"""The ``measured`` bench tier: paper sweeps on the Pallas backend.

Runs the Fig. 1-3 style throughput/latency sweeps with *wall-clock*
time instead of model cycles: every cell is one
``core/locks/pallas_backend.run_measured`` launch — the same ``LockIR``
the sim executes, lowered to a kernel that hammers the lock words
through the device atomics layer. Cells compile for the TPU the process
holds and fail on any other backend. ``BenchConfig.interpret``
(``repro.bench run --interpret``) runs them in the Pallas interpreter
instead, as the CPU tests do: schedule-exact and linearizable, but the
wall numbers then time the interpreter, and every cell records its
``platform`` and ``device_kind`` so they cannot pass for device times.
The backend-agreement table cross-checks the admission structure
against the sim at uniform cost in either mode.

Cells are fronted by the experiment cache under a dedicated
``"measured"`` key kind (``_measured_key``): the key starts from the
same program fingerprint as sim cells but never collides with the sim
``"cell"`` keyspace, and bakes in the backend mode and the device kind,
so interpret runs and runs on different chips cache separately. Cache hit/miss accounting flows through
``store.stats`` like every other cell, so suite-level telemetry
(``BENCH_trend.json`` wall/traces/hit-rate) covers measured runs with
no extra plumbing.

The calibration experiment (``bench/calibrate.py``) closes the
sim->silicon loop: it fits the sim's ``CostModel`` scale to the
measured curves and reports the per-cell fitted-vs-measured error
table that lands in docs/RESULTS.md.
"""
from __future__ import annotations

import hashlib
import json
import time

import numpy as np

from repro.bench import cache as cachemod
from repro.bench import calibrate, sweep
from repro.bench.registry import BenchConfig, emit
from repro.bench.schema import (
    scalars_experiment, sweep_experiment, table_experiment,
)
from repro.core.sim.machine import CostModel

#: the measured lock subset: the paper trio + a DSL-authored pair, kept
#: small because every cell is a real kernel launch (and, in interpret
#: mode, a slice-by-slice emulation)
MEASURED_ALGS = ("reciprocating", "ticket", "mcs", "ttas", "hapax")
#: locks whose round-robin admission order must agree between backends
AGREEMENT_ALGS = ("reciprocating", "mcs", "ticket", "hapax")


def _algs(cfg: BenchConfig) -> tuple:
    return tuple(cfg.algs) if cfg.algs else MEASURED_ALGS


def _rounds(cfg: BenchConfig, n_threads: int) -> int:
    # one sim step is one micro-op slice; a measured round is T slices —
    # match the per-cell op budget so the tiers are comparable
    return max(cfg.n_steps // max(n_threads, 1), 64)


def _measured_key(ir, n_threads: int, rounds: int, seed: int,
                  interpret: bool, device_kind: str) -> str:
    """Content key of a measured cell. Distinct key *kind* from the sim
    ``"cell"`` keyspace (bench/cache.py) — a measured run and a sim run
    of the same program can never collide."""
    fp = cachemod.program_fingerprint(ir)     # duck-types on the IR
    return hashlib.sha256(json.dumps(
        {"v": cachemod.CACHE_KEY_VERSION, "kind": "measured", "fp": fp,
         "T": int(n_threads), "rounds": int(rounds), "seed": int(seed),
         "ncs": int(ir.ncs_max), "cs": ir.cs_mode,
         "backend": "interpret" if interpret else "device",
         "device": device_kind},
        sort_keys=True).encode()).hexdigest()


def measured_cell(alg: str, n_threads: int, rounds: int, *,
                  ncs_max: int = 0, cs_shared=True, seed: int = 0,
                  interpret: bool = False) -> dict:
    """One measured cell, cache-fronted. Returns the summary dict (not
    the ``MeasuredResult`` — the cache stores plain JSON)."""
    import jax

    from repro.core.locks.pallas_backend import resolve_ir, run_measured

    ir = resolve_ir(alg, n_threads, ncs_max=ncs_max, cs_shared=cs_shared)
    store = cachemod.get_cache()
    key = _measured_key(ir, n_threads, rounds, seed, interpret,
                        jax.devices()[0].device_kind)
    s = store.get(key)
    if s is not None:
        if store.enabled:
            store.stats.hits += 1
        return s
    if store.enabled:
        store.stats.misses += 1
    r = run_measured(ir, n_threads, rounds, seed=seed, interpret=interpret)
    s = {
        "lock": r.name, "threads": n_threads, "rounds": rounds,
        "backend": r.backend, "platform": r.platform,
        "device_kind": r.device_kind, "device_count": r.device_count,
        "episodes": r.episodes,
        "per_thread": r.per_thread.tolist(),
        "collisions": r.collisions, "returns": r.returns,
        "aborts": r.aborts, "admission_counts": r.admission_counts,
        "admissions": r.admissions[:64].tolist(),
        "wall_s": round(r.wall_s, 6), "compile_s": round(r.compile_s, 3),
        "throughput_eps": round(r.throughput_eps, 1),
        "episodes_per_kslice": round(r.episodes_per_kslice, 4),
        "latency_slices": round(r.latency_slices, 3),
    }
    if store.enabled:
        store.put(key, s)
    return s


def measured_sweep(algs, cfg: BenchConfig, *, ncs_max: int = 0,
                   cs_shared=True, tag: str = "measured",
                   on_cell=None) -> list:
    """Thread sweep on the measured backend -> schema series list."""
    series = []
    for alg in algs:
        points = []
        for t in cfg.threads:
            t0 = time.time()
            c = measured_cell(alg, t, _rounds(cfg, t), ncs_max=ncs_max,
                              cs_shared=cs_shared, seed=cfg.seed0,
                              interpret=cfg.interpret)
            wall = time.time() - t0
            if on_cell is not None:
                on_cell(alg, t, c)
            points.append({
                "threads": t, "episodes": c["episodes"],
                "throughput_eps": c["throughput_eps"],
                "episodes_per_kslice": c["episodes_per_kslice"],
                "latency_slices": c["latency_slices"],
                "collisions": c["collisions"],
                "wall_s": round(wall, 3),
            })
            if cfg.verbose:
                emit(f"{tag}/{alg}/T{t}",
                     wall / max(c["episodes"], 1) * 1e6,
                     f"eps/ks={c['episodes_per_kslice']:.2f} "
                     f"coll={c['collisions']} [{c['backend']}]")
        series.append({"label": alg, "points": points})
    return series


# --- backend-agreement differential ------------------------------------------

def sim_agreement(alg: str, n_threads: int, admissions,
                  admission_counts: int, *, sim_steps: int, seed: int = 0,
                  limit: int = 48) -> dict:
    """The backend-agreement check: the sim under a *uniform* cost model
    (hit == miss == 1 cycle) dispatches exactly the measured kernel's
    round-robin op schedule, so a measured admission ring must equal the
    sim's admission order and, over the compared prefix (at most
    ``limit`` admissions), its per-thread CS counts. A mismatch means one
    backend's machine semantics drifted."""
    from repro.core.locks.programs import PROGRAMS
    from repro.core.sim.machine import ADM_LOG, run_machine

    uni = CostModel(hit=1, local_miss=1, remote_miss=1)
    prog = PROGRAMS[alg](n_threads, ncs_max=0, cs_shared=True)
    s = run_machine(prog, n_threads, sim_steps, cm=uni, seed=seed)
    if int(s.adm_cnt) > ADM_LOG:
        raise ValueError(f"{alg}: {int(s.adm_cnt)} sim admissions wrap the "
                         f"{ADM_LOG}-entry log; lower sim_steps")
    sim_order = np.asarray(s.adm_log)[:int(s.adm_cnt)]
    pal_order = np.asarray(admissions)[:admission_counts]
    n = min(len(sim_order), len(pal_order), limit)
    sim_cnt = np.bincount(sim_order[:n], minlength=n_threads)
    pal_cnt = np.bincount(pal_order[:n], minlength=n_threads)
    return {
        "compared": n,
        "order_match": bool((sim_order[:n] == pal_order[:n]).all()),
        "cs_counts_match": bool((sim_cnt == pal_cnt).all()),
        "cs_split": "/".join(str(int(x)) for x in pal_cnt),
    }


def agreement_rows(cfg: BenchConfig, algs=AGREEMENT_ALGS,
                   n_threads: int = 3) -> list:
    """:func:`sim_agreement` for each of ``algs``, one table row each."""
    sim_steps = 1_000 if cfg.quick else 3_000
    rounds = 150 if cfg.quick else 400
    rows = []
    for alg in algs:
        c = measured_cell(alg, n_threads, rounds, seed=cfg.seed0,
                          interpret=cfg.interpret)
        a = sim_agreement(alg, n_threads, c["admissions"],
                          c["admission_counts"], sim_steps=sim_steps,
                          seed=cfg.seed0)
        rows.append({"lock": alg, "threads": n_threads, **a,
                     "collisions": c["collisions"]})
        if cfg.verbose:
            emit(f"measured_agree/{alg}", 0.0,
                 f"order_match={a['order_match']} n={a['compared']} "
                 f"coll={c['collisions']}")
    return rows


# --- suite builder ------------------------------------------------------------

def build_measured(cfg: BenchConfig) -> list:
    """The ``measured`` suite: backend catalogue, Fig 1-3 style sweeps on
    the Pallas backend, the backend-agreement table, and the
    CostModel-calibration error table (bench/calibrate.py)."""
    from repro.core.locks.pallas_backend import backends

    exps = [table_experiment(
        "measured_backends", "Execution backends (availability-probed)",
        ("name", "available", "detail"),
        [dict(r) for r in backends()],
        meta={"note": "`repro.bench list --backends` prints this "
                      "catalogue; measured cells run pallas-device, or "
                      "pallas-interpret under --interpret."})]

    algs = _algs(cfg)
    meas: dict = {}
    a = measured_sweep(algs, cfg, ncs_max=0, tag="measured_max_contention",
                       on_cell=lambda al, t, c: meas.__setitem__((al, t), c))
    exps.append(sweep_experiment(
        "measured_fig1a", "Measured Fig. 1a analogue — throughput vs "
        "threads, maximal contention (Pallas backend)", "threads", a))
    if not cfg.quick:
        b = measured_sweep(algs, cfg, ncs_max=250,
                           tag="measured_random_ncs")
        exps.append(sweep_experiment(
            "measured_fig1b", "Measured Fig. 1b analogue — random NCS "
            "delay (Pallas backend)", "threads", b))
        k = measured_sweep(algs, cfg, ncs_max=60, cs_shared="ro",
                           tag="measured_kvstore")
        exps.append(sweep_experiment(
            "measured_fig3", "Measured Fig. 3 analogue — read-only CS, "
            "random key-gen NCS (Pallas backend)", "threads", k))

    rows = agreement_rows(cfg)
    exps.append(table_experiment(
        "measured_agreement", "Backend agreement — sim (uniform cost) vs "
        "Pallas round-robin schedule", ("lock", "threads", "compared",
        "order_match", "cs_counts_match", "cs_split", "collisions"), rows,
        meta={"note": "order_match compares admission-order prefixes; "
                      "collisions counts mutual-exclusion violations "
                      "observed by the in-kernel guard (must be 0)."}))

    fit = calibrate.calibrate(meas, cfg)
    exps.append(table_experiment(
        "measured_calibration", "CostModel calibration — fitted sim "
        "throughput vs measured (per cell)",
        ("lock", "threads", "measured_eps_per_kslice", "sim_eps_per_kcycle",
         "fitted", "rel_err"),
        fit.rows,
        meta={"note": "fit: measured ~= scale * sim(cost model); "
                      "see bench/calibrate.py for the model."}))
    exps.append(scalars_experiment(
        "measured_calibration_fit", "CostModel calibration fit",
        {"scale_kslice_per_kcycle": fit.scale,
         "mean_rel_err": fit.mean_rel_err,
         "max_rel_err": fit.max_rel_err,
         "cost_model": fit.cost_label,
         "candidates_tried": fit.candidates_tried}))
    return exps
