"""Device time inside the host spans named ``span`` divided by the
counter ``per``, times ``scale``: e.g. device microseconds per scan
iteration."""


def read(record: dict, span: str, per: str, scale: float = 1.0):
    t = (record.get("trace") or {}).get("span_device_s", {}).get(span)
    n = record.get("counters", {}).get(per)
    if not t or not n:
        return None
    return t / n * scale
