"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of operation intervals) / window."""


def read(record):
    if record["counts"].get("kind") != "serve" or record["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - record["busy_s"] / record["window_s"])
