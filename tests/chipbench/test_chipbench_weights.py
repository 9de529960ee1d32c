"""The benchmark's weight draw: the starcoder2 tree as pinned, and a
latent-attention MoE tree (norm scales, router and experts) by the same
rules."""
import hashlib

import jax
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import weights

SEED = 2**32 + 3
#: sha256 of the starcoder2-3b smoke tree drawn from SEED (paths, shapes,
#: dtypes and bytes of every leaf in order), as drawn before serve kinds
#: became files of their own
STARCODER2_SMOKE = ("18d9c2235a84d2ece42119da6c9594597bdcf3b6c9cbce56f3f97"
                    "06b96f3d8ac")


def smoke_tree(arch: str):
    from repro.configs import get_config
    from repro.configs.base import smoke_config
    from repro.models import model as M
    cfg = smoke_config(get_config(arch))
    return weights.draw(M.abstract_params(cfg), SEED, cfg.dtype)


def leaves(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in kp):
            np.asarray(leaf, np.float32)
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_starcoder2_draw_is_as_pinned():
    h = hashlib.sha256()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(
            smoke_tree("starcoder2-3b"))[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(kp), str(a.shape), str(a.dtype)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == STARCODER2_SMOKE


def test_deepseek_v2_draws_norms_near_one_and_the_rest_by_fan_in():
    got = leaves(smoke_tree("deepseek-v2-236b"))
    for p in ("layers/attn/q_norm", "layers/attn/kv_norm",
              "dense_layers/attn/kv_norm", "final_norm"):
        assert abs(got[p].mean() - 1) < 0.05 and 0.05 < got[p].std() < 0.15
    fan_in = ["layers/moe/w_router", "layers/moe/wg", "layers/moe/wd",
              "layers/moe/sh_wd", "layers/attn/wkv_b", "layers/attn/wq_b"]
    for p in fan_in:
        x = got[p]
        assert abs(x.mean()) < 0.05 / np.sqrt(x.shape[-2])
        assert x.std() * np.sqrt(x.shape[-2]) == pytest.approx(1, abs=0.06)
    # each layer of a stack has a draw of its own
    assert not np.array_equal(got["layers/moe/wg"][0],
                              got["layers/moe/wg"][1])


def test_a_1d_leaf_that_is_no_norm_scale_is_refused():
    import jax.numpy as jnp
    tree = {"embed": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16),
            "layers": {"attn": {"bias": jax.ShapeDtypeStruct(
                (2, 4), jnp.bfloat16)}}}
    with pytest.raises(H.BenchError, match="layers/attn/bias"):
        weights.draw(tree, SEED)
