"""What every cell shares: finding a cell's files by name, the device
check, host spans, the compile listener, per-layer readers and the
result line.

A cell is found by its name in ``BENCHMARK.json``: its configuration is
the file that entry names, its traffic mix is ``traffic/<traffic>.json``
(whose ``driver`` names the module in ``drivers/`` that runs it), and
each per-layer metric is ``metrics/<metric>.json``, which names a reader
in ``readers/`` and that reader's arguments. A later cell, mix or metric
is added as files, with no edit here.

A model kind is added as two files, with no edit elsewhere: the
configuration's ``kind`` key names ``kinds/<kind>.py`` and its
``reference`` key names ``reference/<reference>.py``. A serve kind
provides ``program_config(cfg)`` (the program's ``ModelConfig`` from the
file; a ``BenchError`` for a key the program cannot honour), the
least-work counts ``prefill_flops(cfg, n, cached)``,
``prefill_bytes(cfg, n)``, ``decode_flops(cfg, contexts)``,
``decode_bytes(cfg, contexts)`` and ``token_flops(cfg, context)``, and
``POOLS``, the names of the executor's pool arrays that an admission
writes. The counts come from the configuration's sizes and the step's
contexts alone, never from what the program reports: a routed-expert
kind counts its expected routed pairs from the configuration, ``top_k x
held / n_experts`` per token for the ``held`` experts the chip holds. A
serve reference provides ``readings(params, prompt, served, cfg, pad_to,
ids, quant)`` (``reference/dense_lm.py``).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(RuntimeError):
    """The run cannot produce a result (no accelerator, a missing file,
    a failed set-up): the harness exits non-zero and prints no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by its path (names may hold ``-``)."""
    name = "chipbench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: where a configuration's ``kind`` and ``reference`` keys find their files
KINDS = HERE / "kinds"
REFERENCES = HERE / "reference"


@functools.cache
def load_named(folder: Path, name: str, what: str):
    """``<folder>/<name>.py``, loaded once a process, so that its jitted
    functions keep their compiled programs from one user to the next."""
    path = folder / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no {what} file {path}: the configuration's "
                         f"{what!r} key names {name!r}")
    return load_module(path)


def load_kind(cfg: dict):
    """The serve kind that the configuration's ``kind`` key names."""
    return load_named(KINDS, cfg["kind"], "kind")


def load_reference(cfg: dict):
    """The plain reference that the configuration's ``reference`` key
    names."""
    return load_named(REFERENCES, cfg["reference"], "reference")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # BENCHMARK.json metric entries
    per_layer: list

    @property
    def driver(self):
        path = HERE / "drivers" / f"{self.traffic['driver']}.py"
        if not path.exists():
            raise BenchError(f"no driver {path.name} for traffic of "
                             f"{self.name}")
        return load_module(path)


def cell_from_files(name: str, config: str, traffic: str,
                    chips: int = 1) -> Cell:
    """A cell from a configuration's name and a traffic mix's name alone,
    with no metrics: for drivers run by tests and by ``readings.py``
    before the cell is in ``BENCHMARK.json``."""
    return Cell(name, chips,
                load_json(HERE / "configs" / f"{config}.json"),
                load_json(HERE / "traffic" / f"{traffic}.json"), [], [])


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench_path = root / "BENCHMARK.json"
    if not bench_path.exists():
        raise BenchError(f"{bench_path} not found")
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


# --- the device ----------------------------------------------------------------

def require_device(chips: int) -> list:
    """The first ``chips`` accelerator devices; a host whose JAX finds no
    TPU, or fewer than ``chips``, is an error and gets no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device {device_kind!r} in "
                         f"peaks.json; add the chip with its source")
    return table[device_kind]


def configure_jax() -> str:
    """The program's own compile-cache directory (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), with every
    program kept, however quick its compile, so that only a checkout's
    first run compiles; the experiment cache of ``repro.bench`` is off."""
    os.environ["REPRO_BENCH_NO_CACHE"] = "1"
    import jax

    from repro.bench import cache as experiment_cache
    from repro.compile_cache import configure_compile_cache
    experiment_cache.configure(enabled=False)
    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


# --- spans and counters --------------------------------------------------------

class Spans:
    """Host spans the benchmark records around its calls into the
    program: ``(name, start_s, end_s)`` on the host clock, kept in
    memory. With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` (named ``cb.<name>``), so that the
    trace reduction sees it on the device trace's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: list = []
        if annotate:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = (self.spans._ann("cb." + self.name)
                    if self.spans.annotate else None)
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.items.append((self.name, self.t0, t1))
        return False


class CompileCounter:
    """Counts JAX backend compiles (a persistent-cache load counts too)
    while ``counting`` is set, through a JAX monitoring listener."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.counting = False
        self.count = 0
        self.names: list = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.counting and event == self.EVENT:
            self.count += 1
            self.names.append(kw.get("fun_name", "?"))


# --- results -------------------------------------------------------------------

@dataclass
class Check:
    """One number compared with its limit: ``ok`` when ``value`` is at
    most ``limit`` (``at_least``: at least)."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if self.value is None or (isinstance(self.value, float)
                                  and math.isnan(self.value)):
            return False
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)


@dataclass
class RunOutput:
    """What a driver hands back: the correctness checks, the counts of
    attempted and failed operations, the end-to-end values by metric
    name, and the record the per-layer readers read."""
    checks: list
    attempted: int
    failed: int
    end_to_end: dict
    device: dict
    record: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def read_per_layer(cell: Cell, record: dict) -> dict:
    """Each per-layer metric of the cell through its reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        spec = load_json(HERE / "metrics" / f"{m['name']}.json")
        reader = load_module(HERE / "readers" / f"{spec['reader']}.py")
        value = reader.read(record, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell: Cell, out: RunOutput, trace: bool) -> dict:
    if trace:
        metrics = read_per_layer(cell, out.record)
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": out.device}
    if trace and out.record.get("breakdown"):
        line["breakdown"] = out.record["breakdown"]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                               "ok": c.ok} for c in out.checks}
    return line


def print_checks(checks: list) -> None:
    for c in checks:
        rel = ">=" if c.at_least else "<="
        print(f"[check] {c.name} = {c.value!r} (limit {rel} {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- one run -------------------------------------------------------------------

@dataclass
class RunContext:
    """What a driver gets: the cell, the run's seed, window and trace
    switch, the time the process started (``setup_s`` counts from it),
    the devices (``None`` in a test that drives a run without a chip)
    and the host spans."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list | None
    spans: Spans = None

    def __post_init__(self):
        if self.spans is None:
            self.spans = Spans(annotate=self.trace)

    def device(self) -> dict:
        if self.devices is None:
            return {"platform": "none", "kind": "none", "count": 0,
                    "memory_peak_bytes": 0}
        return device_record(self.devices)

    def peaks(self) -> dict | None:
        return None if self.devices is None else peaks(
            self.devices[0].device_kind)


class Profiler:
    """The JAX profiler around the traced part of a ``--trace 1`` run,
    written to a temporary directory that ``stop`` reads, reduces and
    removes. The Python tracer is off: it records every Python call of
    the host, which the reduction never reads, and multiplies the time
    the trace takes to write and read."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None
        self.write_s = None

    def start(self) -> None:
        if self.on:
            import tempfile

            import jax
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def halt(self) -> None:
        """Stop tracing and write the trace; ``stop`` reads it later."""
        if self.on and self.write_s is None:
            import jax
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.write_s = time.perf_counter() - t0

    def stop(self) -> dict:
        if not self.on:
            return {}
        import shutil

        from chipbench import trace as tracemod
        self.halt()
        t1 = time.perf_counter()
        try:
            tr = tracemod.load(tracemod.find_xplane(self.dir))
            t2 = time.perf_counter()
            out = tracemod.reduce(tr)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        log(f"[trace] written in {self.write_s:.3f} s, read in "
            f"{t2 - t1:.3f} s ({sum(map(len, tr.devices.values()))} device "
            f"operations), reduced in {time.perf_counter() - t2:.3f} s")
        return out
