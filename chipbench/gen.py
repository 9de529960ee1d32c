"""Serving traffic, in seconds, from a traffic file's parameters.

The shape of the mix follows the repository's multi-tenant trace
generator (``serve/traces.py``), with its clock turned from scheduler
steps into seconds: Zipf-weighted tenants, each with a fixed shared
prompt prefix of whole blocks; a unique tail of whole blocks per
request; Poisson bursts, each of one tenant's requests, spread over a
burst width; lognormal output lengths with a cap.

Every seed sees the same sizes and the same arrival times, in the
window and in the warm-up alike. They are drawn from the file's
``shape_seed``; ``--seed`` then deals the sizes to the arrival slots in
another order, names the tenants (which prompt belongs to which
popularity rank) and draws every token. So two seeds differ in order
and content, never in the amount of work or the shapes compiled.

``mode``: ``open_loop`` gives each request the due time of its slot;
``saturated`` gives every request due time 0, and the driver keeps its
queue full instead of following a clock.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Req:
    rid: int
    due_s: float
    tenant: int
    tokens: np.ndarray          # prompt: the tenant's prefix, then the tail
    prefix_len: int             # tokens of the prompt that are the prefix
    max_new: int


def _sizes(tr: dict, rng, n: int):
    """``n`` requests' (tenant rank, tail blocks, output tokens)."""
    nt = tr["n_tenants"]
    w = 1.0 / np.arange(1, nt + 1) ** tr["zipf_s"]
    w /= w.sum()
    lo_u, hi_u = tr["unique_blocks"]
    ranks = rng.choice(nt, size=n, p=w)
    tails = rng.integers(lo_u, hi_u + 1, size=n)
    outs = np.minimum(
        tr["output_cap"],
        np.maximum(1, np.round(tr["output_median"] * np.exp(
            tr["output_sigma"] * rng.standard_normal(n))))).astype(int)
    return ranks, tails, outs


def _arrivals(tr: dict, rng, seconds: float):
    """Sorted arrival times (s) in ``[0, seconds)``: Poisson burst starts
    at ``rate / mean burst size``, a geometric number of requests per
    burst, scattered over ``burst_width_s``."""
    if "rate_per_s" not in tr:
        raise ValueError("the traffic has no rate_per_s: it is set to 0.8 "
                         "x the knee that readings.py sweeps on the chip")
    burst_rate = tr["rate_per_s"] / (1.0 + tr["burst_extra_mean"])
    times, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / burst_rate)
        if t >= seconds:
            break
        size = 1 + rng.geometric(1.0 / tr["burst_extra_mean"])
        times += [t + off for off in rng.uniform(0.0, tr["burst_width_s"],
                                                  size) if t + off < seconds]
    return np.sort(times)


class Tenants:
    """Tenant prompts: each popularity rank's prefix length (whole
    blocks, from the shape seed) and tokens (from ``seed``); ``seed``
    also decides which tenant id holds which rank."""

    def __init__(self, tr: dict, vocab: int, seed: int):
        shape = np.random.default_rng([tr["shape_seed"], 1])
        lo, hi = tr["shared_blocks"]
        self.blocks = shape.integers(lo, hi + 1, size=tr["n_tenants"])
        rng = np.random.default_rng([seed, 10])
        self.ids = rng.permutation(tr["n_tenants"])
        self.bt = tr["block_tokens"]
        self.vocab = vocab
        self._seed = seed
        self._prompts: dict = {}

    def prefix(self, rank: int) -> np.ndarray:
        p = self._prompts.get(rank)
        if p is None:
            rng = np.random.default_rng([self._seed, 11, int(rank)])
            p = self._prompts[rank] = rng.integers(
                1, self.vocab, int(self.blocks[rank]) * self.bt,
                dtype=np.int32)
        return p


def _requests(tenants, sizes, due, rng, rid0=0) -> list:
    ranks, tails, outs = sizes
    out = []
    for i, (rank, tail, new) in enumerate(zip(ranks, tails, outs)):
        pre = tenants.prefix(rank)
        own = rng.integers(1, tenants.vocab, int(tail) * tenants.bt,
                           dtype=np.int32)
        out.append(Req(rid=rid0 + i, due_s=float(due[i]),
                       tenant=int(tenants.ids[rank]),
                       tokens=np.concatenate([pre, own]),
                       prefix_len=len(pre), max_new=int(new)))
    return out


def schedule(tr: dict, vocab: int, seed: int, seconds: float) -> list:
    """The requests of one window, in due order."""
    tenants = Tenants(tr, vocab, seed)
    shape = np.random.default_rng([tr["shape_seed"], 2])
    if tr["mode"] == "open_loop":
        due = _arrivals(tr, shape, seconds)
    elif tr["mode"] == "saturated":
        due = np.zeros(tr["saturated_requests"])
    else:
        raise ValueError(f"unknown traffic mode {tr['mode']!r}")
    sizes = _sizes(tr, shape, len(due))
    deal = np.random.default_rng([seed, 12]).permutation(len(due))
    sizes = tuple(s[deal] for s in sizes)
    return _requests(tenants, sizes, due,
                     np.random.default_rng([seed, 13]))


def warmup(tr: dict, vocab: int, seed: int) -> list:
    """Set-up's stretch of the same mix from a stream of its own: the
    same tenants, ``warmup_requests`` fresh requests."""
    tenants = Tenants(tr, vocab, seed)
    n = tr["warmup_requests"]
    sizes = _sizes(tr, np.random.default_rng([tr["shape_seed"], 3]), n)
    deal = np.random.default_rng([seed, 21]).permutation(n)
    sizes = tuple(s[deal] for s in sizes)
    return _requests(tenants, sizes, np.zeros(n),
                     np.random.default_rng([seed, 20]), rid0=10**6)
