"""Model FLOP utilization of the whole step: the model FLOPs of every
token processed (counter ``flops``) over the wall seconds spent in the
step (counter ``seconds``) times the chip's peak bf16 FLOP/s, in
percent."""


def read(record: dict, flops: str, seconds: str):
    c, peaks = record.get("counters", {}), record.get("peaks")
    if not peaks or not c.get(seconds) or not c.get(flops):
        return None
    return 100.0 * c[flops] / (c[seconds] * peaks["bf16_flops_per_s"])
