"""Host time per engine tick: each `ServeEngine.tick` span of the
benchmark minus the device busy time inside it, averaged over ticks."""

from bench.trace import overlap


def read(record):
    if record["counts"].get("kind") != "serve":
        return None
    tr = record["trace"]
    ticks = [s for s in tr.spans if s.name == "bench.tick"]
    if not ticks:
        return None
    busy = tr.busy(tr.devices[0])
    host = [t.dur - overlap(busy, t.start, t.end) for t in ticks]
    return 1e3 * sum(host) / len(host)
