"""Ahead-of-time compiles of the device paths for a described v5e chip.

Nothing runs: each test lowers a jitted program on shapes placed on one
chip of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what the chip would refuse (unsupported kernel
primitives, memory spaces, programs that do not fit). The topology is
described inside a module fixture, so importing this file touches no
TPU library, and the tests skip where no topology can be described.
"""
import re

import numpy as np
import pytest

from repro.configs import get_config

LOCKS = ("reciprocating", "mcs")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape stand-ins of ``tree``'s arrays, placed on ``sharding``."""
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("T", [8, 64])
@pytest.mark.parametrize("alg", LOCKS)
def test_lock_kernel_compiles(one_chip, alg, T):
    from repro.core.locks.pallas_backend import (
        build_measured, initial_buffers, resolve_ir,
    )
    ir = resolve_ir(alg, T)
    fn = build_measured(ir, T, 2_000)
    compiled = fn.lower(*_on(one_chip, initial_buffers(ir, T, 0))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sim_grid_runner_compiles(one_chip):
    """The ``SimEngine.grid`` runner at T=64 over 16 points (2 machine
    topologies x 8 seeds), the shape of the on-chip sim grid."""
    from repro.core.sim.engine import (
        SimEngine, Workload, _lower_host, _lower_sched_host,
    )
    T, n = 64, 16
    eng = SimEngine("reciprocating", n_threads=T)
    wl = Workload(0, "rw", 20_000)
    lows = [_lower_host(t, T) for t in ("epyc-2s", "xeon-4s")] * 8
    slo = _lower_sched_host(None, T)
    args = ([np.zeros(n, np.int32)]
            + [np.stack([lo[i] for lo in lows]) for i in range(6)]
            + [np.stack([slo[i]] * n) for i in range(4)])
    eng._runner(T, wl, n).lower(*_on(one_chip, args)).compile()
    assert eng.compiles == 1


def _scan_body(hlo: str):
    """Ops of the scan's ``while`` body in compiled HLO text: a counter
    of the body's own instructions, and the set of instructions inside
    every computation it calls (fusions, reductions), found by name."""
    import collections
    import re
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps[head.group(1)] = []
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)

    def ops(lines):
        return [m.group(1) for m in
                (re.search(r"= .*?[\])}] ([a-z][\w\-]*)\(", ln)
                 for ln in lines) if m]
    bodies = re.findall(r"while\(.*body=%?([\w.\-]+)", hlo)
    body = max(bodies, key=lambda b: len(comps[b]))
    inner, todo, seen = set(), [body], {body}
    while todo:
        for c in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                            "\n".join(comps[todo.pop()])):
            if c not in seen:
                seen.add(c)
                todo.append(c)
                inner.update(ops(comps[c]))
    return collections.Counter(ops(comps[body])), inner


#: fusions in the scan body of the sim cell's runner per lock, as the
#: one-hot stepper compiles for a v5e (the indexed stepper had 74-82)
SCAN_FUSIONS = {"reciprocating": 44, "mcs": 35, "clh": 43, "ticket": 40}


@pytest.mark.parametrize("alg", sorted(SCAN_FUSIONS))
def test_sim_scan_body_selects_not_gathers(one_chip, alg):
    """The sim cell's runner (T=64, 16 points, NCS 0-249): the stepper
    reads and writes the chosen thread and word by one-hot selects, so
    its ``while`` body holds no gather or scatter by per-point data and
    no transpose of ``sharers``, and its kernel count stays down."""
    from repro.core.sim.engine import (
        SimEngine, Workload, _lower_host, _lower_sched_host,
    )
    T, n = 64, 16
    eng = SimEngine(alg, n_threads=T)
    wl = Workload(250, "rw", 20_000)
    lows = [_lower_host(t, T) for t in ("epyc-2s", "xeon-4s")] * 8
    slo = _lower_sched_host(None, T)
    args = ([np.zeros(n, np.int32)]
            + [np.stack([lo[i] for lo in lows]) for i in range(6)]
            + [np.stack([slo[i]] * n) for i in range(4)])
    hlo = eng._runner(T, wl, n).lower(*_on(one_chip, args)) \
        .compile().as_text()
    body, inner = _scan_body(hlo)
    indexed = {"gather", "scatter", "transpose"} & (set(body) | inner)
    assert not indexed, indexed
    assert 0 < body["fusion"] <= SCAN_FUSIONS[alg], body


def _two_layers(one_chip, arch):
    """``arch`` at its published widths, cut to 2 layers, with abstract
    bf16 parameters on the chip."""
    from repro.models import model as M_
    cfg = get_config(arch).replace(n_layers=2)
    return cfg, _on(one_chip, M_.abstract_params(cfg))


@pytest.fixture(scope="module")
def starcoder(one_chip):
    return _two_layers(one_chip, "starcoder2-3b")


def _whole_pool_copies(hlo: str, shape: tuple) -> list:
    """The ``copy`` instructions of compiled HLO text whose result is an
    array of the whole pool's ``shape``."""
    dims = ",".join(map(str, shape))
    return re.findall(rf"%\S+ = \w+\[{dims}\]\{{[^}}]*\}} copy\(", hlo)


def test_starcoder2_prefill_compiles(one_chip, starcoder):
    import jax

    from repro.models import decode as D_
    from repro.sharding.ctx import trivial_ctx
    cfg, params = starcoder
    ctx = trivial_ctx()

    def prefill(p, toks, last):       # as PagedModelExecutor runs it
        logits, cache = D_.prefill_step(p, {"tokens": toks}, cfg, ctx,
                                        last_index=last)
        return (logits[0], *D_.cache_to_blocks(cache, 16))
    toks = np.zeros((1, 512), np.int32)
    last = np.zeros((1,), np.int32)
    compiled = jax.jit(prefill).lower(
        params, *_on(one_chip, (toks, last))).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("arch", ["starcoder2-3b", "granite-3-2b"])
def test_starcoder2_paged_decode_compiles(one_chip, arch):
    """The paged decode step for KV x hd of 2 x 128 and 8 x 64 writes its
    blocks into the donated pools in place: no copy of a whole pool
    (pools shaped (P, L, block, KV, hd) took four or six a step)."""
    import jax

    from repro.models import decode as D_
    from repro.sharding.ctx import trivial_ctx
    cfg, params = _two_layers(one_chip, arch)
    ctx = trivial_ctx()
    B, max_seq, block = 8, 1024, 16
    nb = max_seq // block
    P = 1 + nb * (B + 2)
    shape = D_.pool_shape(P, cfg.n_layers, block, cfg.n_kv_heads, cfg.hd)
    pool = jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one_chip)
    ints = _on(one_chip, (np.zeros((B, nb), np.int32),
                          np.zeros((B,), np.int32),
                          np.zeros((B,), np.int32)))
    step = jax.jit(lambda p, kp, vp, tb, po, tk: D_.paged_decode_step(
        p, kp, vp, tb, po, tk, cfg, ctx), donate_argnums=(1, 2))
    compiled = step.lower(params, pool, pool, *ints).compile()
    assert compiled.memory_analysis() is not None
    assert not _whole_pool_copies(compiled.as_text(), shape)


@pytest.mark.parametrize("donated,most", [(False, 1), (True, 0)])
def test_paged_admission_write_copies(one_chip, donated, most):
    """An admission's write of its prefilled blocks into a starcoder2-3b
    pool, ``pool.at[tgt].set(blocks)``: the engine runs it eagerly, which
    copies the pool once; donated, it writes in place."""
    import jax

    from repro.models import decode as D_
    cfg = get_config("starcoder2-3b").replace(n_layers=2)
    n = 40                               # a 640-token prompt's blocks
    shape = D_.pool_shape(8000, cfg.n_layers, 16, cfg.n_kv_heads, cfg.hd)
    pool, blocks = (jax.ShapeDtypeStruct(s, cfg.dtype, sharding=one_chip)
                    for s in (shape, (n,) + shape[1:]))
    tgt = _on(one_chip, np.zeros((n,), np.int32))
    write = jax.jit(lambda p, t, b: p.at[t].set(b),
                    donate_argnums=(0,) if donated else ())
    hlo = write.lower(pool, tgt, blocks).compile().as_text()
    assert len(_whole_pool_copies(hlo, shape)) <= most


def test_starcoder2_weight_draw_compiles(one_chip):
    """The weight draw at published widths, cut to 2 layers: one program
    whose float32 draws fuse with their bf16 casts, so it needs almost
    no memory beyond the weights it returns."""
    import jax

    from repro.models import model as M_
    from repro.models import params as P_
    cfg = get_config("starcoder2-3b").replace(n_layers=2)
    leaves, _ = jax.tree.flatten(M_.param_specs(cfg, 1), is_leaf=P_.is_param)
    key = jax.ShapeDtypeStruct((2,), np.uint32, sharding=one_chip)
    m = P_._draw.lower(key, tuple(leaves), cfg.dtype).compile() \
        .memory_analysis()
    assert m.temp_size_in_bytes < 0.01 * m.output_size_in_bytes
