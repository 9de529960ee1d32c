"""A counter the driver kept, as it is."""


def read(record: dict, key: str):
    return record.get("counters", {}).get(key)
