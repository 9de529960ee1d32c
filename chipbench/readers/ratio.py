"""100 x one counter over another, e.g. the prompt tokens served from
shared prefix blocks over all prompt tokens admitted."""


def read(record: dict, num: str, den: str):
    c = record.get("counters", {})
    if not c.get(den):
        return None
    return 100.0 * c.get(num, 0) / c[den]
