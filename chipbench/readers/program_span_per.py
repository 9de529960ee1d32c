"""Host time per call from the program's own spans (``repro.tracing``):
for each of the window's calls (root spans named ``per``), the seconds
of its spans named in ``spans`` less those of its spans named in
``minus``; the median over the calls, times ``scale``. The median, as a
traced run's window is one round of calls, and the call after the
profiler's trace is written runs 0.1-0.2 s slow (TPU v5 lite).

The driver leaves the program's spans in its process: the window's calls
are the last ``counters[calls]`` roots named ``per`` in the program's
ring of spans, since no call into the program follows the window. None
where there is nothing to read: no such counter, a program without
``repro.tracing``, or a ring that no longer holds the window whole."""
import statistics


def window(ring: list, n: int, per: str, kept: int):
    """call id -> its spans, for the last ``n`` calls whose root is
    named ``per``; None unless all ``n`` are there with every span: a
    ring that has held ``kept`` spans has dropped its oldest ones, which
    must then all have ended before the first of these calls began."""
    roots = [r for r in ring if r.name == per and r.parent is None][-n:]
    if len(roots) < n or (len(ring) >= kept
                          and ring[0].end_s > roots[0].start_s):
        return None
    calls = {r.call: [] for r in roots}
    for r in ring:
        if r.call in calls:
            calls[r.call].append(r)
    return calls


def per_call(calls: dict, spans, minus=()) -> float:
    def total(rs, names):
        return sum(r.end_s - r.start_s for r in rs if r.name in names)
    return statistics.median(total(rs, spans) - total(rs, minus)
                             for rs in calls.values())


def read(record: dict, spans: list, per: str, calls: str,
         minus: list = (), scale: float = 1.0):
    n = record.get("counters", {}).get(calls)
    if not n:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    got = window(tracing.snapshot()["ring"], n, per, tracing.RING)
    if got is None:
        return None
    return per_call(got, spans, minus) * scale
