"""Work counts for the roofline and utilization readers, from the
configuration's sizes alone (never from the implementation).

Dense GQA decoder (starcoder2-3b and its kind), per layer: q/o
projections ``2 * d * H * hd`` weights, k/v ``2 * d * KV * hd``, the MLP
``2 * d * d_ff`` (``3 *`` for a gated MLP); embedding and unembedding
``vocab * d`` each. A matmul costs 2 FLOPs per weight and token.
Causal attention over a context of ``c`` positions costs ``4 * H * hd``
FLOPs per position attended (scores and weighted values).
"""
from __future__ import annotations


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


def prefill_flops(cfg: dict, n: int) -> float:
    """FLOPs to prefill ``n`` prompt tokens: every weight matmul for every
    token, causal attention (token ``i`` attends to ``i + 1``
    positions) and the one unembedding of the last token."""
    m = dense_dims(cfg)
    mat = 2 * n * m["L"] * m["layer_weights"]
    attn = 4 * m["H"] * m["hd"] * m["L"] * n * (n + 1) / 2
    return mat + attn + 2 * m["V"] * m["d"]


def prefill_bytes(cfg: dict, n: int, dtype_bytes: int = 2) -> float:
    """Least bytes to prefill ``n`` tokens: the layer weights and the
    unembedding read once, the prompt's KV written once."""
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


def sim_bytes_per_step(threads: int, regs: int = 8) -> int:
    """Least bytes one simulated micro-op needs: the dispatch keys of all
    threads (4 bytes each) to pick the earliest-ready one; that thread's
    state row (program counter, registers, current op of 4 words and 15
    scalar counters and clocks of 4 bytes), read and written; and the
    addressed line (value, owner, last writer, 4 bytes each, and a
    one-byte sharer flag per thread), read and written."""
    row = 4 * (1 + regs + 4 + 15)
    line = 3 * 4 + threads
    return 4 * threads + 2 * row + 2 * line
