"""Work counts against hand arithmetic at a small size."""
import pytest

from chipbench import work
from chipbench.kinds import dense_lm

CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
       "vocab_size": 10, "tie_word_embeddings": False}
# per layer: q, o 8x8 each; k, v 8x4 each; MLP 8x16 twice
LAYER = 64 + 64 + 32 + 32 + 128 + 128


def test_dense_dims():
    assert dense_lm.dense_dims(CFG)["layer_weights"] == LAYER
    assert dense_lm.weight_bytes(CFG) == (3 * LAYER + 2 * 80 + 7 * 8) * 2
    assert dense_lm.kv_bytes_per_token(CFG) == 2 * 3 * 1 * 4 * 2


# with 3 of 5 tokens cached, tokens 3 and 4 attend to 4 and 5 positions
@pytest.mark.parametrize("cached,attended", [(0, 1 + 2 + 3 + 4 + 5),
                                             (3, 4 + 5)])
def test_prefill(cached, attended):
    n = 5
    attn = 4 * 2 * 4 * 3 * attended
    assert (dense_lm.prefill_flops(CFG, n, cached)
            == 2 * (n - cached) * 3 * LAYER + attn + 2 * 80)
    assert dense_lm.prefill_bytes(CFG, n) == (3 * LAYER + 80) * 2 + n * 48


def test_decode_counts_each_rows_real_context():
    ctxs = [0, 9]
    per = 2 * 3 * LAYER + 2 * 80
    assert dense_lm.decode_flops(CFG, ctxs) == 2 * per + 4 * 2 * 4 * 3 * (1 + 10)
    assert dense_lm.decode_bytes(CFG, ctxs) == (3 * LAYER + 80) * 2 + 11 * 48


def test_starcoder2_3b_sizes():
    import json
    from pathlib import Path
    cfg = json.loads((Path(__file__).resolve().parents[2] / "chipbench"
                      / "configs" / "starcoder2-3b.json").read_text())
    # 3,180,518,400 parameters in bf16, KV 30,720 bytes per token
    assert dense_lm.weight_bytes(cfg) == 6_361_036_800
    assert dense_lm.kv_bytes_per_token(cfg) == 30_720
    # a token at position 0: 2 FLOPs per matmul weight (all but the
    # embedding and the norms), and attention to one position
    matmul = 6_361_036_800 // 2 - 49152 * 3072 - 61 * 3072
    assert dense_lm.token_flops(cfg, 0) == 2 * matmul + 4 * 24 * 128 * 30


def test_sim_bytes_per_step():
    # keys 4T; row 4 (1 + 8 + 4 + 15) twice; line (12 + T) twice
    assert work.sim_bytes_per_step(64) == 256 + 224 + 152
