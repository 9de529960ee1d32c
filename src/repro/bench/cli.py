"""Command-line interface: ``python -m repro.bench <command>``.

Commands:

* ``list``                      — show the suite catalogue; ``--programs``
                                  enumerates the registered lock specs
                                  (phase anatomy, registers, memory
                                  regions), ``--topologies`` the machine
                                  topology presets, ``--schedulers`` the
                                  hostile-OS scheduler presets,
                                  ``--routers`` the fleet-gateway routing
                                  policies (serve/gateway.py),
                                  ``--cache`` the experiment-cache state
                                  plus each suite's latest trend entry
                                  (wall time / hit rate from
                                  ``BENCH_trend.json``),
                                  ``--suites`` the suites; flags combine
* ``run --suite paper --out BENCH_paper.json``
                                — run a suite, write the schema-valid JSON
                                  result, and (for the ``paper`` suite, or
                                  whenever ``--report`` is given) render
                                  ``docs/RESULTS.md`` from it. Cells are
                                  served from the content-addressed
                                  experiment cache (``bench/cache.py``)
                                  when their inputs are unchanged;
                                  ``--no-cache`` forces regeneration
                                  (the store is still refreshed) and
                                  ``--cache-dir`` moves the store. Every
                                  run appends a harness-performance
                                  entry to ``BENCH_trend.json`` next to
                                  ``--out`` (``--trend`` to relocate,
                                  ``--no-trend`` to skip)
* ``report --in BENCH_paper.json [--out docs/RESULTS.md]``
                                — re-render markdown from an existing result
* ``validate --in BENCH_paper.json``
                                — schema-check a result document
* ``verify [--lock a,b] [--exhaustive]``
                                — run the static analyzer + small-scope
                                  model checker over the lock zoo
                                  (``core/locks/cfg.py`` /
                                  ``core/locks/verify.py``), print the
                                  verified property matrix, splice it
                                  into ``docs/RESULTS.md``, and exit
                                  non-zero (with minimal counterexample
                                  traces) on any violation.
                                  ``--exhaustive`` re-certifies at 3
                                  threads instead of 2
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from repro.bench import cache as cachemod
from repro.bench import registry, report, schema
from repro.compile_cache import configure_compile_cache

DEFAULT_REPORT = "docs/RESULTS.md"
DEFAULT_TREND = "BENCH_trend.json"


def _parse_threads(text: str) -> tuple:
    return tuple(int(t) for t in text.split(",") if t)


def _build_config(args) -> registry.BenchConfig:
    kw = {}
    if args.threads:
        threads = _parse_threads(args.threads)
        bad = [t for t in threads if t < 1]
        if bad:
            raise ValueError(f"--threads values must be >= 1, got {bad}")
        kw["threads"] = threads
    if args.steps is not None:
        kw["n_steps"] = args.steps
    if args.replicas is not None:
        kw["n_replicas"] = args.replicas
    if args.algs:
        from repro.core.locks.programs import PROGRAMS
        algs = tuple(args.algs.split(","))
        bad = [a for a in algs if a not in PROGRAMS]
        if bad:
            raise ValueError(f"unknown lock program(s) {bad}; "
                             f"available: {sorted(PROGRAMS)}")
        kw["algs"] = algs
    kw["seed0"] = args.seed
    kw["quick"] = args.quick
    kw["verbose"] = not args.no_progress
    kw["interpret"] = args.interpret
    return registry.BenchConfig(**kw)


def _print_cache_status(trend_path: str) -> None:
    store = cachemod.get_cache()
    d = store.describe()
    state = "enabled" if d["enabled"] else "DISABLED"
    print(f"# experiment cache (bench/cache.py, key v"
          f"{cachemod.CACHE_KEY_VERSION})")
    print(f"{'store':12s} {d['root']} — {state}, {d['entries']} entries, "
          f"{d['bytes'] / 1024:.1f} KiB")
    trend = schema.load_trend(trend_path)
    latest: dict = {}
    for e in trend["entries"]:
        latest[e.get("suite")] = e       # last entry per suite wins
    if not latest:
        print(f"{'trend':12s} no {trend_path} yet — populated by "
              "`run` (per-suite wall time / traces / hit rate)")
        return
    print(f"{'trend':12s} latest per suite from {trend_path}:")
    for name in sorted(latest):
        e = latest[name]
        hits, misses = e.get("cache_hits"), e.get("cache_misses")
        rate = e.get("cache_hit_rate")
        cache_txt = ("no cacheable cells" if not (hits or misses) else
                     f"{hits}/{hits + misses} hits "
                     f"({(rate or 0) * 100:.0f}%)")
        quick = " (quick)" if e.get("quick") else ""
        print(f"{'':12s} {name:12s} wall={e.get('wall_s')}s "
              f"traces={e.get('xla_traces')} {cache_txt}{quick}")


def cmd_list(args) -> int:
    show_programs = getattr(args, "programs", False)
    show_topologies = getattr(args, "topologies", False)
    show_schedulers = getattr(args, "schedulers", False)
    show_routers = getattr(args, "routers", False)
    show_backends = getattr(args, "backends", False)
    show_cache = getattr(args, "cache", False)
    show_properties = getattr(args, "properties", False)
    show_suites = (getattr(args, "suites", False)
                   or not (show_programs or show_topologies
                           or show_schedulers or show_routers
                           or show_backends or show_cache
                           or show_properties))
    if show_suites:
        print("# suites")
        for name in registry.names():
            s = registry.get(name)
            print(f"{name:12s} {s.title}")
            print(f"{'':12s}   {s.description}")
    if show_programs:
        from repro.core.locks.programs import (
            NEW_VARIANTS, PROGRAMS, describe_program,
        )
        print("# lock programs (LockSpec phase anatomy — "
              "core/locks/specs.py)")
        for name in sorted(PROGRAMS):
            d = describe_program(name)
            phases = " ".join(
                f"{p}:{len(steps)}" for p, steps in d["phases"].items()
                if steps)
            regions = ", ".join(f"{n}[{sz} {kind}]"
                                for n, sz, kind in d["regions"])
            mem = ", ".join(list(d["words"]) + ([regions] if regions else []))
            tag = "  (new variant)" if name in NEW_VARIANTS else ""
            print(f"{name:15s} {phases}{tag}")
            print(f"{'':15s}   regs: {', '.join(d['regs']) or '-'}; "
                  f"mem: {mem}")
    if show_topologies:
        from repro.core.sim.topology import catalogue
        print("# machine topologies (core/sim/topology.py; outermost "
              "tier first, @cost = transfer cycles, * = NUMA-remote)")
        for name, summary in catalogue():
            print(f"{name:12s} {summary}")
        print(f"{'':12s} pass presets/shorthand to SimEngine(topology=...) "
              "or bench_lock(cost=...)")
    if show_schedulers:
        from repro.core.sim.sched import catalogue
        print("# hostile-OS schedulers (core/sim/sched.py; quanta in "
              "simulator cycles, dedicated = never preempted)")
        for name, summary in catalogue():
            print(f"{name:12s} {summary}")
        print(f"{'':12s} pass presets/shorthand to "
              "SimEngine(scheduler=...) or .grid(schedulers=[...])")
    if show_routers:
        from repro.serve.gateway import catalogue
        print("# fleet gateway routers (serve/gateway.py; targets are "
              "always slack-bearing replicas — SERVING.md §8)")
        for name, summary in catalogue():
            print(f"{name:14s} {summary}")
        print(f"{'':14s} pass names to FleetGateway(router=...) or the "
              "gateway bench suite")
    if show_backends:
        from repro.core.locks.pallas_backend import backends
        print("# execution backends (availability-probed; "
              "core/locks/pallas_backend.py)")
        for row in backends():
            mark = "available" if row["available"] else "UNAVAILABLE"
            print(f"{row['name']:17s} {mark:12s} {row['detail']}")
        print(f"{'':17s} the `measured` suite runs pallas-device, or "
              "pallas-interpret under `run --interpret`")
    if show_properties:
        from repro.core.locks import verify as verify_mod
        print("# verified/declared lock properties (structural analysis "
              "— core/locks/cfg.py; `verify` adds the model check)")
        verdicts = verify_mod.verify_all(model=False)
        print(verify_mod.render_matrix(verdicts))
    if show_cache:
        _print_cache_status(getattr(args, "trend", None) or DEFAULT_TREND)
    return 0


def cmd_verify(args) -> int:
    from repro.core.locks import verify as verify_mod
    names = tuple(n for n in (args.lock or "").split(",") if n)
    t0 = time.time()

    def progress(v):
        if not args.no_progress:
            state = "ok" if v.ok else "FAIL"
            cert = v.check.certificate if v.check else "structural only"
            print(f"# {v.name:26s} {state}  {cert}", flush=True)

    try:
        verdicts = verify_mod.verify_all(
            names=names, exhaustive=args.exhaustive,
            episodes=args.episodes, max_states=args.max_states,
            on_result=progress)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2
    print()
    print(verify_mod.render_matrix(verdicts))
    bad = [v for v in verdicts if not v.ok]
    for v in bad:
        print(f"\n# {v.name}: VERIFICATION FAILED")
        if v.error:
            print(f"  compile/spec error: {v.error}")
        for viol in v.structural_violations:
            print(f"  structural: {viol}")
        if v.check is not None and not v.check.ok:
            print(f"  model check ({v.check.violation}): {v.check.detail}")
            print(f"  minimal counterexample "
                  f"({len(v.check.trace)} transitions):")
            for line in v.check.trace:
                print(f"    {line}")
    scope = "T=3" if args.exhaustive else "T=2"
    print(f"\n# {len(verdicts) - len(bad)}/{len(verdicts)} locks certified "
          f"({scope}, {time.time() - t0:.1f}s)")
    if not args.no_results and not names:
        from repro.bench import report as reportmod
        note = ("Generated by `python -m repro.bench verify"
                + (" --exhaustive" if args.exhaustive else "") + "`.")
        reportmod.splice_section(
            args.results, reportmod.VERIFY_HEADER,
            reportmod.verify_section_lines(verdicts, note))
        print(f"# spliced matrix into {args.results}")
    return 1 if bad else 0


def cmd_run(args) -> int:
    cfg = _build_config(args)
    cachemod.configure(root=args.cache_dir or None,
                       read=not args.no_cache)
    t0 = time.time()
    if cfg.verbose:
        print("name,us_per_call,derived")
        print(f"# === suite {args.suite} ===", flush=True)
    doc = registry.run_suite(args.suite, cfg)
    schema.save_result(doc, args.out)
    print(f"# wrote {args.out} ({len(doc['experiments'])} experiments, "
          f"{time.time() - t0:.1f}s)")
    if not args.no_trend:
        trend_path = args.trend or os.path.join(
            os.path.dirname(args.out) or ".", DEFAULT_TREND)
        schema.append_trend(trend_path, schema.trend_entry(doc))
        h = doc["harness"]
        print(f"# trend -> {trend_path} (wall={h['wall_s']}s "
              f"traces={h['xla_traces']} cache {h['cache_hits']} hit / "
              f"{h['cache_misses']} miss)")
    report_path = args.report
    if report_path is None and args.suite == "paper" and not args.no_report:
        report_path = DEFAULT_REPORT
    if report_path:
        report.write_report(doc, report_path)
        print(f"# rendered {report_path}")
    return 0


def cmd_report(args) -> int:
    doc = schema.load_result(args.infile)
    out = args.out or DEFAULT_REPORT
    report.write_report(doc, out)
    print(f"# rendered {out} from {args.infile}")
    return 0


def cmd_validate(args) -> int:
    import json
    with open(args.infile) as f:
        doc = json.load(f)
    errors = schema.validate_result(doc)
    if errors:
        print(f"{args.infile}: INVALID")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"{args.infile}: valid {schema.SCHEMA_VERSION} "
          f"(suite={doc['suite']}, {len(doc['experiments'])} experiments)")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.bench",
        description="Registry-driven benchmark harness (paper Figs 1-3, "
                    "Table 1, fairness; see `list`).")
    sub = ap.add_subparsers(dest="command", required=True)

    ls = sub.add_parser("list",
                        help="show the suite / lock-program catalogue")
    ls.add_argument("--suites", action="store_true",
                    help="enumerate registered suites (the default)")
    ls.add_argument("--programs", action="store_true",
                    help="enumerate registered lock specs with their "
                         "phase anatomy")
    ls.add_argument("--topologies", action="store_true",
                    help="enumerate the machine-topology preset "
                         "catalogue (core/sim/topology.py)")
    ls.add_argument("--schedulers", action="store_true",
                    help="enumerate the hostile-OS scheduler preset "
                         "catalogue (core/sim/sched.py)")
    ls.add_argument("--routers", action="store_true",
                    help="enumerate the fleet-gateway routing policy "
                         "catalogue (serve/gateway.py)")
    ls.add_argument("--backends", action="store_true",
                    help="probe and enumerate the execution backends "
                         "(sim / pallas-interpret / pallas-device — "
                         "core/locks/pallas_backend.py)")
    ls.add_argument("--properties", action="store_true",
                    help="print the per-lock verified/declared property "
                         "matrix (structural analysis only; see `verify`)")
    ls.add_argument("--cache", action="store_true",
                    help="show experiment-cache state and each suite's "
                         "latest trend entry (BENCH_trend.json)")
    ls.add_argument("--trend", default=None,
                    help=f"trend log to read for --cache "
                         f"(default: {DEFAULT_TREND})")
    ls.set_defaults(fn=cmd_list)

    run = sub.add_parser("run", help="run a suite and write its JSON result")
    run.add_argument("--suite", required=True)
    run.add_argument("--out", required=True,
                     help="output JSON path (e.g. BENCH_paper.json)")
    run.add_argument("--report", default=None,
                     help="also render markdown to this path "
                          f"(default for --suite paper: {DEFAULT_REPORT})")
    run.add_argument("--no-report", action="store_true",
                     help="skip the default markdown render")
    run.add_argument("--quick", action="store_true",
                     help="tiny grid for smoke runs")
    run.add_argument("--threads", default="",
                     help="comma-separated thread counts, e.g. 1,2,4,8")
    run.add_argument("--steps", type=int, default=None,
                     help="micro-steps per cell")
    run.add_argument("--replicas", type=int, default=None,
                     help="vmapped replica ensemble size per cell")
    run.add_argument("--algs", default="",
                     help="comma-separated lock subset (default: suite's)")
    run.add_argument("--seed", type=int, default=0)
    run.add_argument("--no-progress", action="store_true")
    run.add_argument("--no-cache", action="store_true",
                     help="force regeneration: skip cache lookups "
                          "(results are still stored for later runs)")
    run.add_argument("--cache-dir", default="",
                     help="experiment-cache directory (default: "
                          f"{cachemod.DEFAULT_ROOT} or "
                          "$REPRO_BENCH_CACHE_DIR)")
    run.add_argument("--trend", default=None,
                     help="harness-performance trend log path (default: "
                          f"{DEFAULT_TREND} next to --out)")
    run.add_argument("--no-trend", action="store_true",
                     help="skip the trend-log append")
    run.add_argument("--interpret", action="store_true",
                     help="run the measured tier's Pallas kernels in the "
                          "interpreter (default: compile for the TPU, and "
                          "fail without one)")
    run.set_defaults(fn=cmd_run)

    rep = sub.add_parser("report",
                         help="re-render markdown from an existing result")
    rep.add_argument("--in", dest="infile", required=True)
    rep.add_argument("--out", default=None)
    rep.set_defaults(fn=cmd_report)

    val = sub.add_parser("validate", help="schema-check a result document")
    val.add_argument("--in", dest="infile", required=True)
    val.set_defaults(fn=cmd_validate)

    ver = sub.add_parser(
        "verify",
        help="statically verify the lock zoo and model-check all "
             "interleavings at small scope")
    ver.add_argument("--lock", default="",
                     help="comma-separated lock subset (default: all; "
                          "subsets skip the RESULTS.md splice)")
    ver.add_argument("--exhaustive", action="store_true",
                     help="model-check at 3 threads (default certifies "
                          "at 2)")
    ver.add_argument("--episodes", type=int, default=2,
                     help="lock episodes per thread in the model check")
    ver.add_argument("--max-states", type=int, default=200_000,
                     help="state-expansion budget per lock (exceeding it "
                          "downgrades the certificate to 'bounded')")
    ver.add_argument("--results", default=DEFAULT_REPORT,
                     help="markdown file to splice the property matrix "
                          f"into (default: {DEFAULT_REPORT})")
    ver.add_argument("--no-results", action="store_true",
                     help="skip the RESULTS.md splice")
    ver.add_argument("--no-progress", action="store_true")
    ver.set_defaults(fn=cmd_verify)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    try:
        return args.fn(args)
    except registry.UnknownSuiteError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
    except FileNotFoundError as e:
        print(f"error: no such file: {e.filename}", file=sys.stderr)
    except ValueError as e:           # invalid result document
        print(f"error: {e}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
