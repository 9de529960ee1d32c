"""Host time per call: the wall seconds of the spans named ``span``
(counter ``wall``) minus the device time inside them, over the counter
``per``, times ``scale``."""


def read(record: dict, span: str, wall: str, per: str, scale: float = 1.0):
    t = (record.get("trace") or {}).get("span_device_s", {}).get(span)
    c = record.get("counters", {})
    if t is None or not c.get(per) or not c.get(wall):
        return None
    return (c[wall] - t) / c[per] * scale
