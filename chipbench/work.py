"""Work counts of the lock simulator, for its roofline reader, from the
cell's sizes alone (never from the implementation). A serve kind's
counts are in its own file, ``kinds/<kind>.py``.
"""
from __future__ import annotations


def sim_bytes_per_step(threads: int, regs: int = 8) -> int:
    """Least bytes one simulated micro-op needs: the dispatch keys of all
    threads (4 bytes each) to pick the earliest-ready one; that thread's
    state row (program counter, registers, current op of 4 words and 15
    scalar counters and clocks of 4 bytes), read and written; and the
    addressed line (value, owner, last writer, 4 bytes each, and a
    one-byte sharer flag per thread), read and written."""
    row = 4 * (1 + regs + 4 + 15)
    line = 3 * 4 + threads
    return 4 * threads + 2 * row + 2 * line
