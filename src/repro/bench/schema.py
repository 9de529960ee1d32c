"""JSON result schema for the benchmark harness (``repro.bench/v1``).

Every suite run produces one *result document*:

```
{
  "schema": "repro.bench/v1",
  "suite": "paper",
  "created_unix": 1753779600.0,
  "config": {...BenchConfig...},
  "environment": {"python": "...", "jax": "...", "backend": "cpu",
                  "platform": "cpu", "device_kind": "cpu",
                  "device_count": 1},
  "experiments": [<experiment>, ...]
}
```

Experiments come in four kinds, covering everything the paper's §7
evaluation reports:

* ``sweep``   — curves over an x axis (throughput-vs-threads, Figs 1-3):
                ``{"x": "threads", "y": [metric, ...], "series":
                [{"label": "mcs", "points": [{"threads": 1, ...}, ...]}]}``
* ``table``   — row/column facts (Table 1 coherence traffic):
                ``{"columns": [...], "rows": [{col: val, ...}, ...]}``
* ``scalars`` — a flat name->value mapping (Table 2 cycle, §9 fairness)
* ``hist``    — labelled histograms sharing one bin axis (bypass
                distributions): ``{"bins": [...], "series":
                [{"label": "lifo", "counts": [...]}]}``

The ``x`` axis of a sweep is whatever the suite varies — ``threads`` for
the paper figures, ``offered_load`` (requests/step) for the ``serve``
suite. The serve suite (docs/SERVING.md §6) adds three experiments, all
expressed in the existing kinds: ``serve_policy_load`` (sweep —
throughput / tail wait / prefix-hit curves per admission policy),
``serve_pool`` (table — starvation + paged-KV pool counters at the
heaviest load), and ``serve_engine_smoke`` (scalars — the model-backed
paged engine run end-to-end; full runs only, values may nest one dict of
pool counters).

The ``locks-ext`` suite (DESIGN.md §L2 extended lock zoo) likewise uses
the existing kinds: ``locksext_sweep`` (sweep — DSL-authored variants vs
paper baselines over threads), ``locksext_profile`` (table — per-lock
phase anatomy ``spec_steps``, coherence profile, and the observed
``bypass_bound`` from the admission log), and ``locksext_park`` (table —
spin_then_park throughput/latency vs the ``CostModel`` park/unpark
costs).

The ``topology`` suite (DESIGN.md §L1 machine models) also reuses the
existing kinds: ``topology_grid`` (table — every lock across the
SMP/NUMA/CCX/interleaved machine roster), ``topology_remote_scaling``
(sweep over ``nodes`` — remote misses per episode vs NUMA node count),
``topology_placement`` (table — contiguous vs interleaved pinning), and
``topology_compile`` (scalars — the SimEngine.grid one-jit-per-shape
compile accounting that CI asserts on).

The ``hostile`` suite (DESIGN.md §L1 scheduler model) is all existing
kinds too: ``hostile_grid`` (table — locks × quantum × oversubscription
with throughput-vs-dedicated ratios, preemption and abort counts),
``hostile_lhp`` (table — lock-holder-preemption penalty per lock),
``hostile_abort`` (table — the timed-wait locks' abort rate up the
hostility ladder), and ``hostile_compile`` (scalars — the scheduler-axis
compile accounting; CI asserts ``compiles_per_grid <= 1`` here as well,
pinning that schedulers batch as stacked data).

The ``gateway`` suite (docs/SERVING.md §8 fleet tier) stays inside the
same kinds: ``gateway_routers`` (table — routing policies across
fleet-level TTFT/TPOT/goodput, global cache-hit rate, load imbalance
and live tree size on the seeded multi-tenant trace),
``gateway_load`` (sweep over ``offered_load`` — the same metrics per
router as the arrival rate rises), and ``gateway_scale`` (scalars —
prefix vs random vs round_robin at 100k requests quick / 1M full,
nesting one dict per router, with the O(requests) bookkeeping bound
asserted inside the builder).

Result documents additionally carry a ``"harness"`` block (written by
``registry.run_suite``): suite wall time, fresh XLA traces paid, and
experiment-cache hit/miss/store counts for the run. The block is
advisory — ``validate_result`` ignores it — but it is what the *trend*
document aggregates.

The trend document (``repro.bench-trend/v1``, default path
``BENCH_trend.json`` next to the result) is an append-only, capped
log of harness performance: one compact entry per suite run
(``suite``, ``quick``, ``experiments``, ``wall_s``, ``xla_traces``,
``cache_hits``/``cache_misses``/``cache_stores``, ``cache_hit_rate``,
``created_unix``), so harness speed regressions are visible in review
diffs next to ``BENCH_paper.json``. ``append_trend`` is tolerant of a
missing or corrupt file (it restarts the log) — the trend is telemetry,
never a build input.

``validate_result`` is the single source of truth for well-formedness;
``save_result``/``load_result`` refuse to write or return an invalid
document, so a BENCH_*.json on disk is schema-valid by construction.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from typing import Any

SCHEMA_VERSION = "repro.bench/v1"
TREND_SCHEMA_VERSION = "repro.bench-trend/v1"
TREND_LIMIT = 200           # entries kept per trend file (oldest dropped)
KINDS = ("sweep", "table", "scalars", "hist")


def environment_info() -> dict:
    """The interpreter, the jax version and the device every number in
    the document was produced on (``jax.devices()[0]``)."""
    import jax
    dev = jax.devices()[0]
    return {"python": sys.version.split()[0], "jax": jax.__version__,
            "backend": jax.default_backend(), "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def new_result(suite: str, config: dict | None = None,
               environment: dict | None = None) -> dict:
    return {
        "schema": SCHEMA_VERSION,
        "suite": suite,
        "created_unix": time.time(),
        "config": config or {},
        "environment": (environment if environment is not None
                        else environment_info()),
        "experiments": [],
    }


# --- experiment constructors -------------------------------------------------

def sweep_experiment(name: str, title: str, x: str, series: list,
                     y: list | None = None, meta: dict | None = None) -> dict:
    if y is None:
        keys: list = []
        for s in series:
            for p in s.get("points", []):
                for k in p:
                    if k != x and k not in keys:
                        keys.append(k)
        y = keys
    return {"name": name, "kind": "sweep", "title": title, "x": x, "y": y,
            "series": series, "meta": meta or {}}


def table_experiment(name: str, title: str, columns: list, rows: list,
                     meta: dict | None = None) -> dict:
    return {"name": name, "kind": "table", "title": title,
            "columns": list(columns), "rows": rows, "meta": meta or {}}


def scalars_experiment(name: str, title: str, values: dict,
                       meta: dict | None = None) -> dict:
    return {"name": name, "kind": "scalars", "title": title,
            "values": values, "meta": meta or {}}


def hist_experiment(name: str, title: str, bins: list, series: list,
                    meta: dict | None = None) -> dict:
    return {"name": name, "kind": "hist", "title": title, "bins": list(bins),
            "series": series, "meta": meta or {}}


# --- validation --------------------------------------------------------------

def _err(errors: list, where: str, msg: str) -> None:
    errors.append(f"{where}: {msg}")


def _check_series(errors: list, where: str, exp: dict) -> None:
    x = exp.get("x")
    if not isinstance(x, str):
        _err(errors, where, "sweep needs a string 'x' axis name")
        return
    series = exp.get("series")
    if not isinstance(series, list) or not series:
        _err(errors, where, "sweep needs a non-empty 'series' list")
        return
    for i, s in enumerate(series):
        w = f"{where}.series[{i}]"
        if not isinstance(s, dict) or not isinstance(s.get("label"), str):
            _err(errors, w, "series needs a string 'label'")
            continue
        pts = s.get("points")
        if not isinstance(pts, list) or not pts:
            _err(errors, w, "series needs a non-empty 'points' list")
            continue
        for j, p in enumerate(pts):
            if not isinstance(p, dict) or x not in p:
                _err(errors, f"{w}.points[{j}]",
                     f"point must be a dict containing the x key {x!r}")
            elif not isinstance(p[x], (int, float)):
                _err(errors, f"{w}.points[{j}]", f"x value {p[x]!r} not numeric")


def _check_experiment(errors: list, i: int, exp: Any) -> None:
    where = f"experiments[{i}]"
    if not isinstance(exp, dict):
        _err(errors, where, "experiment must be a dict")
        return
    name = exp.get("name")
    if not isinstance(name, str) or not name:
        _err(errors, where, "experiment needs a non-empty string 'name'")
    kind = exp.get("kind")
    if kind not in KINDS:
        _err(errors, where, f"kind {kind!r} not in {KINDS}")
        return
    if not isinstance(exp.get("title"), str):
        _err(errors, where, "experiment needs a string 'title'")
    if kind == "sweep":
        _check_series(errors, where, exp)
    elif kind == "table":
        cols = exp.get("columns")
        if not isinstance(cols, list) or not all(
                isinstance(c, str) for c in cols):
            _err(errors, where, "table needs a list[str] 'columns'")
        if not isinstance(exp.get("rows"), list):
            _err(errors, where, "table needs a list 'rows'")
        else:
            for j, r in enumerate(exp["rows"]):
                if not isinstance(r, dict):
                    _err(errors, f"{where}.rows[{j}]", "row must be a dict")
    elif kind == "scalars":
        if not isinstance(exp.get("values"), dict):
            _err(errors, where, "scalars needs a dict 'values'")
    elif kind == "hist":
        bins = exp.get("bins")
        if not isinstance(bins, list) or not bins:
            _err(errors, where, "hist needs a non-empty 'bins' list")
            return
        for j, s in enumerate(exp.get("series") or []):
            w = f"{where}.series[{j}]"
            if not isinstance(s, dict) or not isinstance(s.get("label"), str):
                _err(errors, w, "hist series needs a string 'label'")
            elif (not isinstance(s.get("counts"), list)
                  or len(s["counts"]) != len(bins)):
                _err(errors, w, "hist series 'counts' must match bins length")


def validate_result(doc: Any) -> list:
    """Return a list of problems (empty == schema-valid)."""
    errors: list = []
    if not isinstance(doc, dict):
        return ["document must be a dict"]
    if doc.get("schema") != SCHEMA_VERSION:
        _err(errors, "schema", f"expected {SCHEMA_VERSION!r}, "
             f"got {doc.get('schema')!r}")
    if not isinstance(doc.get("suite"), str) or not doc.get("suite"):
        _err(errors, "suite", "needs a non-empty string suite name")
    exps = doc.get("experiments")
    if not isinstance(exps, list):
        _err(errors, "experiments", "must be a list")
        exps = []
    names = [e.get("name") for e in exps if isinstance(e, dict)]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        _err(errors, "experiments", f"duplicate experiment names: {sorted(dupes)}")
    for i, exp in enumerate(exps):
        _check_experiment(errors, i, exp)
    try:
        json.dumps(doc)
    except (TypeError, ValueError) as e:
        _err(errors, "document", f"not JSON-serializable: {e}")
    return errors


def save_result(doc: dict, path: str) -> None:
    errors = validate_result(doc)
    if errors:
        raise ValueError("refusing to write invalid result:\n  "
                         + "\n  ".join(errors))
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write("\n")


# --- the trend log -----------------------------------------------------------

def trend_entry(doc: dict) -> dict:
    """Compact trend-log entry from a result document's harness block."""
    h = doc.get("harness") or {}
    return {
        "suite": doc.get("suite"),
        "quick": bool((doc.get("config") or {}).get("quick")),
        "experiments": len(doc.get("experiments") or []),
        "wall_s": h.get("wall_s"),
        "xla_traces": h.get("xla_traces"),
        "cache_hits": h.get("cache_hits"),
        "cache_misses": h.get("cache_misses"),
        "cache_stores": h.get("cache_stores"),
        "cache_hit_rate": h.get("cache_hit_rate"),
        "created_unix": doc.get("created_unix"),
    }


def load_trend(path: str) -> dict:
    """The trend document at ``path``; a fresh empty one if the file is
    missing or unreadable (the trend is telemetry, never a build
    input)."""
    with contextlib.suppress(OSError, json.JSONDecodeError):
        with open(path) as f:
            doc = json.load(f)
        if (isinstance(doc, dict)
                and doc.get("schema") == TREND_SCHEMA_VERSION
                and isinstance(doc.get("entries"), list)):
            return doc
    return {"schema": TREND_SCHEMA_VERSION, "entries": []}


def append_trend(path: str, entry: dict) -> dict:
    """Append one run's entry to the trend log at ``path`` (capped at
    ``TREND_LIMIT`` entries) and return the updated document."""
    doc = load_trend(path)
    doc["entries"] = (doc["entries"] + [entry])[-TREND_LIMIT:]
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return doc


def load_result(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    errors = validate_result(doc)
    if errors:
        raise ValueError(f"{path} is not a valid {SCHEMA_VERSION} document:"
                         "\n  " + "\n  ".join(errors))
    return doc
