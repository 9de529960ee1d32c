"""Ahead-of-time compiles of the device paths for a described v5e chip.

Nothing runs: each test lowers a jitted program on shapes placed on one
chip of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what the chip would refuse (unsupported kernel
primitives, memory spaces, programs that do not fit). The topology is
described inside a module fixture, so importing this file touches no
TPU library, and the tests skip where no topology can be described.
"""
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


@pytest.fixture(scope="module")
def starcoder(one_chip):
    """starcoder2-3b at its published widths, cut to 2 layers, with
    abstract bf16 parameters on the chip."""
    from repro.models import model as M_
    cfg = get_config("starcoder2-3b").replace(n_layers=2)
    return cfg, _on(one_chip, M_.abstract_params(cfg))


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


def test_starcoder2_paged_decode_compiles(one_chip, starcoder):
    import jax

    from repro.models import decode as D_
    from repro.sharding.ctx import trivial_ctx
    cfg, params = starcoder
    ctx = trivial_ctx()
    B, max_seq, block = 8, 1024, 16
    nb = max_seq // block
    P = 1 + nb * (B + 2)
    pool = jax.ShapeDtypeStruct(
        (P, cfg.n_layers, block, cfg.n_kv_heads, cfg.hd), cfg.dtype,
        sharding=one_chip)
    ints = _on(one_chip, (np.zeros((B, nb), np.int32),
                          np.zeros((B,), np.int32),
                          np.zeros((B,), np.int32)))
    step = jax.jit(lambda p, kp, vp, tb, po, tk: D_.paged_decode_step(
        p, kp, vp, tb, po, tk, cfg, ctx), donate_argnums=(1, 2))
    compiled = step.lower(params, pool, pool, *ints).compile()
    assert compiled.memory_analysis() is not None


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
