"""Share of the traced training window in which no operation ran on the
device: 1 - (union of operation intervals) / window, averaged over the
chips used."""


def read(record):
    if record["counts"].get("kind") != "train" or record["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - record["busy_s"] / record["window_s"])
