"""The ``q``-th percentile (linear between order statistics) of a sample
the driver kept, e.g. each request's wait from due to admitted."""
import numpy as np


def read(record: dict, key: str, q: float):
    x = record.get("samples", {}).get(key)
    if not x:
        return None
    return float(np.percentile(np.asarray(x, float), q))
