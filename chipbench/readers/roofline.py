"""Share of a roofline: the least time the chip could take for the work
done inside the host spans named ``span`` (the larger of its FLOPs over
peak FLOP/s and its bytes over peak bandwidth, from the counters named
``flops`` and ``bytes``), over the device time inside those spans, in
percent. Nothing to read (no trace, no such span, no work) gives None."""


def read(record: dict, span: str, flops: str | None = None,
         bytes: str | None = None):
    t = (record.get("trace") or {}).get("span_device_s", {}).get(span)
    peaks = record.get("peaks")
    c = record.get("counters", {})
    if not t or not peaks:
        return None
    least = max(c.get(flops, 0) / peaks["bf16_flops_per_s"] if flops else 0,
                c.get(bytes, 0) / peaks["hbm_bytes_per_s"] if bytes else 0)
    if least <= 0:
        return None
    return 100.0 * least / t
