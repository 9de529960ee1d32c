"""Suite registry for the benchmark harness.

A *suite* is a named, registered builder that turns a ``BenchConfig`` into
a list of schema experiments (see ``repro.bench.schema``). Suites compose:
the ``paper`` suite reuses the same builders the per-figure suites
register, so ``run --suite paper`` and ``run --suite coherence`` cannot
drift apart.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, replace
from typing import Callable

from repro.bench import schema


@dataclass(frozen=True)
class BenchConfig:
    """Knobs shared by every suite; ``quick`` shrinks the grid for smoke
    runs (CI / pytest) without changing any code path."""
    threads: tuple = (1, 2, 4, 8, 16, 24)
    n_steps: int = 12_000
    n_replicas: int = 2
    numa_above: int = 8       # thread counts above this use 2 NUMA nodes
    seed0: int = 0
    quick: bool = False
    algs: tuple = ()          # () => suite default (usually all programs)
    verbose: bool = True
    interpret: bool = False   # measured tier: Pallas interpreter, not TPU

    def resolved(self) -> "BenchConfig":
        """Apply ``quick`` shrinkage — but only to knobs still at their
        class defaults, so explicit --threads/--steps/--replicas win."""
        if not self.quick:
            return self
        d = BenchConfig()
        return replace(
            self,
            threads=(1, 2, 4) if self.threads == d.threads else self.threads,
            n_steps=1_500 if self.n_steps == d.n_steps else self.n_steps,
            n_replicas=(1 if self.n_replicas == d.n_replicas
                        else self.n_replicas))

    def to_json(self) -> dict:
        d = asdict(self)
        d["threads"] = list(self.threads)
        d["algs"] = list(self.algs)
        return d


@dataclass(frozen=True)
class Suite:
    name: str
    title: str
    description: str
    build: Callable          # (BenchConfig) -> list[experiment dict]
    tags: tuple = ()


_REGISTRY: dict = {}


def register(name: str, title: str, description: str, tags: tuple = ()):
    """Decorator: register ``fn(cfg) -> [experiment, ...]`` as a suite."""
    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"suite {name!r} already registered")
        _REGISTRY[name] = Suite(name=name, title=title,
                                description=description, build=fn, tags=tags)
        return fn
    return deco


class UnknownSuiteError(KeyError):
    pass


def get(name: str) -> Suite:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownSuiteError(
            f"unknown suite {name!r}; available: {names()}") from None


def names() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded() -> None:
    # Built-in suites live in repro.bench.suites; importing it populates
    # the registry exactly once (idempotent thanks to sys.modules).
    from repro.bench import suites  # noqa: F401


def run_suite(name: str, cfg: BenchConfig | None = None) -> dict:
    """Build a suite into a schema-valid result document.

    The document carries a ``"harness"`` block — wall time, fresh XLA
    traces (the process-wide ``engine.trace_count()`` delta, so traces
    paid by throwaway engines are counted too), and experiment-cache
    hit/miss/store deltas — which ``BENCH_trend.json`` aggregates
    across runs (see ``schema.trend_entry``).
    """
    import time

    from repro.bench import cache as cachemod
    from repro.core.sim import engine as enginemod

    suite = get(name)
    cfg = (cfg or BenchConfig()).resolved()
    t0 = time.time()
    traces0 = enginemod.trace_count()
    store = cachemod.get_cache()
    stats0 = store.stats.snapshot()
    doc = schema.new_result(suite.name, config=cfg.to_json())
    doc["experiments"] = suite.build(cfg)
    stats = store.stats.snapshot()
    hits = stats["hits"] - stats0["hits"]
    misses = stats["misses"] - stats0["misses"]
    doc["harness"] = {
        "wall_s": round(time.time() - t0, 3),
        "xla_traces": enginemod.trace_count() - traces0,
        "cache_enabled": store.enabled,
        "cache_read": store.read,
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_stores": stats["stores"] - stats0["stores"],
        "cache_hit_rate": (round(hits / (hits + misses), 4)
                           if hits + misses else None),
    }
    errors = schema.validate_result(doc)
    if errors:
        raise RuntimeError(f"suite {name!r} produced an invalid document:"
                           "\n  " + "\n  ".join(errors))
    return doc


def emit(name: str, us_per_call: float, derived: str) -> None:
    """Progress line in the historical ``name,us_per_call,derived`` CSV
    format shared with the legacy ``benchmarks/run.py`` driver."""
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)
