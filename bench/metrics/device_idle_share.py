"""Device: share of the traced engine.step() spans in which no operation
ran on the device, in percent."""


def read(record):
    tr = record["trace"]
    if tr is None or not tr["steps"]:
        return None
    span = sum(s["span_ns"] for s in tr["steps"].values())
    busy = sum(s["busy_ns"] for s in tr["steps"].values())
    return 100.0 * (1.0 - busy / span)
