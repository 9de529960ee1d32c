"""Serve kind ``dense_lm``: a dense decoder-only LM with grouped-query
attention (starcoder2-3b and its kind), run through the program's paged
``InferenceEngine``. ``harness.py``'s docstring gives what a serve kind
provides.

The program's ``ModelConfig`` takes every size from the file; the file's
activation, norm, bias and dtype must be the ones the program runs
(``FIXED``), and a sliding window must not bind below ``max_seq``.

Work counts for the roofline and utilization readers, from the
configuration's sizes and the step's contexts alone (never from the
implementation). Per layer: q/o projections ``2 * d * H * hd`` weights,
k/v ``2 * d * KV * hd``, the MLP ``2 * d * d_ff`` (``3 *`` for a gated
MLP); embedding and unembedding ``vocab * d`` each. A matmul costs 2
FLOPs per weight and token. Causal attention over a context of ``c``
positions costs ``4 * H * hd`` FLOPs per position attended (scores and
weighted values).
"""
from __future__ import annotations

from chipbench import harness as H

#: keys of a dense_lm file the program has no switch for, and the only
#: values it runs
FIXED = {"use_bias": False, "norm_type": "rms_norm",
         "hidden_act": "gelu_pytorch_tanh", "torch_dtype": "bfloat16"}
#: the executor's pool arrays, which an admission writes
POOLS = ("k_pool", "v_pool")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the file's architecture, with the
    file's values: the file is what runs."""
    from repro.configs import get_config
    for k, v in FIXED.items():
        if cfg.get(k) != v:
            raise H.BenchError(f"{cfg['name']}: {k}={cfg.get(k)!r}; the "
                               f"program runs only {v!r}")
    window = cfg.get("sliding_window")
    if window is not None and window < cfg["serving"]["max_seq"]:
        raise H.BenchError(f"{cfg['name']}: the program attends to every "
                           f"position; a {window}-token window would bind")
    return get_config(cfg["program_arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], mlp_type="gelu",
        sliding_window=0)


def dense_dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    mlp_mats = 3 if cfg.get("gated_mlp") else 2
    layer = (2 * d * H * hd + 2 * d * KV * hd
             + mlp_mats * d * cfg["intermediate_size"])
    return {"L": cfg["num_hidden_layers"], "d": d, "H": H, "KV": KV,
            "hd": hd, "V": cfg["vocab_size"], "layer_weights": layer}


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Bytes of every weight matrix and norm vector (the embedding is
    read by row, but its bytes are counted as held, not read)."""
    m = dense_dims(cfg)
    embed = m["V"] * m["d"] * (1 if cfg.get("tie_word_embeddings") else 2)
    norms = (2 * m["L"] + 1) * m["d"]
    return (m["L"] * m["layer_weights"] + embed + norms) * dtype_bytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    m = dense_dims(cfg)
    return 2 * m["L"] * m["KV"] * m["hd"] * dtype_bytes


def prefill_flops(cfg: dict, n: int, cached: int = 0) -> float:
    """Least FLOPs to prefill ``n`` prompt tokens whose first ``cached``
    already have their KV in the cache (a prefix hit): every weight
    matmul for each of the other ``n - cached`` tokens, their causal
    attention (token ``i`` attends to ``i + 1`` positions) and the one
    unembedding of the last token. A program that computes the cached
    tokens again does work this does not count."""
    m = dense_dims(cfg)
    mat = 2 * (n - cached) * m["L"] * m["layer_weights"]
    attn = (4 * m["H"] * m["hd"] * m["L"]
            * (n * (n + 1) - cached * (cached + 1)) / 2)
    return mat + attn + 2 * m["V"] * m["d"]


def prefill_bytes(cfg: dict, n: int, dtype_bytes: int = 2) -> float:
    """Least bytes to prefill ``n`` tokens: the layer weights and the
    unembedding read once, the prompt's KV written once (a cached
    prefix's KV is read once instead: the same bytes)."""
    m = dense_dims(cfg)
    weights = (m["L"] * m["layer_weights"] + m["V"] * m["d"]) * dtype_bytes
    return weights + n * kv_bytes_per_token(cfg, dtype_bytes)


def decode_flops(cfg: dict, contexts) -> float:
    """FLOPs of one decode step over live rows whose incoming token sits
    at position ``c`` (so it attends to ``c + 1`` positions)."""
    m = dense_dims(cfg)
    per_token = 2 * m["L"] * m["layer_weights"] + 2 * m["V"] * m["d"]
    return sum(per_token + 4 * m["H"] * m["hd"] * m["L"] * (c + 1)
               for c in contexts)


def decode_bytes(cfg: dict, contexts, dtype_bytes: int = 2) -> float:
    """Least bytes of one decode step: the weights once, and each live
    row's real context of KV (not a gather of the whole ``max_seq``)."""
    m = dense_dims(cfg)
    weights = (m["L"] * m["layer_weights"] + m["V"] * m["d"]) * dtype_bytes
    kv = kv_bytes_per_token(cfg, dtype_bytes)
    return weights + sum((c + 1) * kv for c in contexts)


def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs to process one token at position ``context``."""
    return decode_flops(cfg, [context])
