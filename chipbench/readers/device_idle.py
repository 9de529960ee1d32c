"""Share of the traced window in which no operation ran on the device:
100 x (1 - busy / window), busy being the union of operation intervals
averaged over the devices used."""


def read(record: dict):
    tr = record.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
