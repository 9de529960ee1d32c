"""Reduction of a JAX profiler trace to the numbers the readers need.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``: the
device planes' operation events and the host's ``cb.*`` spans (the
benchmark's own ``TraceAnnotation`` s), all in seconds on the trace's
clock. ``reduce`` then computes, inside the traced window (the
``cb.window`` span):

* each device's busy time, the union of its operation intervals, and
  its mean over the devices used (those with an operation in the
  window);
* the device time inside each kind of host span (the union clipped to
  every span of that name, summed over spans, averaged over devices);
* the operations that took most device time, and the longest idle gaps,
  each labelled with the innermost host span around its middle.

Operations are not matched by program name: the device time of a call is
the time inside the host span that blocks on it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass

WINDOW = "cb.window"
PREFIX = "cb."
#: device lines holding one event per operation, in order of preference
OP_LINES = ("XLA Ops", "Ops")
#: layouts and comments in an HLO instruction's text, left out of its name
HLO_NOISE = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
NAME_LEN = 80


def op_name(text: str) -> str:
    """An operation's name as the breakdown lists it: its HLO text
    without layouts and comments, cut to ``NAME_LEN`` characters, which
    keeps the instruction and the start of its shape."""
    return HLO_NOISE.sub("", text)[:NAME_LEN]


@dataclass
class Trace:
    devices: dict        # device plane name -> [(op name, start s, end s)]
    spans: list          # [(span name, start s, end s)] host cb.* spans


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            names: dict = {}
            evs = devices[plane.name] = []
            for ev in line.events:
                d = ev.duration_ns
                if d > 0:
                    text = ev.name
                    name = names.get(text)
                    if name is None:
                        name = names[text] = op_name(text)
                    evs.append((name, ev.start_ns * 1e-9,
                                (ev.start_ns + d) * 1e-9))
        elif plane.name.startswith("/host:"):
            for ln in lines.values():
                spans += [(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9)
                          for ev in ln.events if ev.name.startswith(PREFIX)]
    return Trace(devices, spans)


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


class Covered:
    """Length of any ``[a, b]`` that merged intervals cover, by bisection
    over their prefix sums."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]
        for s, e in merged:
            self.before.append(self.before[-1] + e - s)

    def upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(x, self.ends[i]) - self.starts[i]

    def __call__(self, a: float, b: float) -> float:
        return max(0.0, self.upto(b) - self.upto(a))


def gaps(merged, a: float, b: float) -> list:
    """Uncovered stretches ``(start, end)`` of ``[a, b]``."""
    out, t = [], a
    for s, e in merged:
        if e <= a or s >= b:
            continue
        if s > t:
            out.append((t, min(s, b)))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def label_at(spans, t: float) -> str:
    """Name of the innermost (shortest) host span around ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and name != WINDOW and (
                best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0][len(PREFIX):] if best else "outside_spans"


def reduce(tr: Trace, top: int = 10) -> dict:
    """Busy and idle time, device time per host span, top operations and
    the longest idle gaps inside the ``cb.window`` span."""
    win = [(a, b) for n, a, b in tr.spans if n == WINDOW]
    if not win or not tr.devices:
        return {}
    w0, w1 = win[0]
    spans = [s for s in tr.spans if s[1] < w1 and s[2] > w0]
    # the devices used: those that ran an operation in the window
    used = {k: evs for k, evs in tr.devices.items()
            if any(b > w0 and a < w1 for _, a, b in evs)}
    if not used:
        return {}
    n_dev = len(used)
    busy = 0.0
    per_span: dict = {}
    ops: dict = {}
    idle: list = []
    for i, (_, evs) in enumerate(sorted(used.items())):
        merged = union((a, b) for _, a, b in evs if b > w0 and a < w1)
        covered = Covered(merged)
        busy += covered(w0, w1)
        for name, a, b in spans:
            key = name[len(PREFIX):]
            n, t = per_span.get(key, (0, 0.0))
            per_span[key] = (n + 1, t + covered(max(a, w0), min(b, w1)))
        for name, a, b in evs:
            if b > w0 and a < w1:
                ops[name] = ops.get(name, 0.0) + min(b, w1) - max(a, w0)
        if i == 0:
            idle = gaps(merged, w0, w1)
    # a span of each name is counted once per device: average them
    device_s = {k: t / n_dev for k, (n, t) in per_span.items()}
    counts = {k: n // n_dev for k, (n, t) in per_span.items()}
    top_ops = sorted(((k, v / n_dev) for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:top]
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": w1 - w0,
        "busy_s": busy / n_dev,
        "devices": n_dev,
        "span_device_s": device_s,
        "span_count": counts,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[label_at(spans, (a + b) / 2), b - a]
                      for a, b in longest],
    }
