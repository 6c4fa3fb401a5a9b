"""Operations and bytes the algorithm needs, computed from shapes.  These are
the least a launch could do, not what the program does: padding, recomputed
tiles and gathered-but-unused cache slots do not count, so a share of the
roofline built on them cannot pass 100%."""

from __future__ import annotations


def param_count(hf: dict) -> dict:
    """Parameters by role, from the model's config.json."""
    E, F, L = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"]
    H = hf["num_attention_heads"]
    K = hf.get("num_key_value_heads", H)
    D = hf.get("head_dim") or E // H
    V = hf["vocab_size"]
    layer = E * H * D + 2 * E * K * D + H * D * E + 3 * E * F
    embed = V * E
    head = 0 if hf.get("tie_word_embeddings") else V * E
    return {"layers": L * layer, "embed": embed, "lm_head": head,
            # what one token's forward multiplies through: every layer and
            # the output head (the input embedding is a gather)
            "matmul": L * layer + V * E,
            "total": L * layer + embed + head}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    H = hf["num_attention_heads"]
    K = hf.get("num_key_value_heads", H)
    D = hf.get("head_dim") or hf["hidden_size"] // H
    return 2 * hf["num_hidden_layers"] * K * D * dtype_bytes


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns (one token for every live
    lane each): every matmul parameter is read once a column and the live
    lanes' cached keys and values once a column, spread over the chips.
    ``lane_tokens`` is the sum over the columns of the live context tokens."""
    p = param_count(hf)
    weight_bytes = p["matmul"] * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    matmul parameter and token, plus attention's 4 * heads * head_dim FLOPs
    for every (query, key) pair of the causal triangle (``attn_pairs``,
    summed over the requests), in every layer."""
    H = hf["num_attention_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    p = param_count(hf)
    # the output head runs for one position of each request, not for all:
    # leave it out, the share errs low by under a percent
    flops = 2.0 * p["layers"] * new_tokens + 4.0 * H * D * hf["num_hidden_layers"] * attn_pairs
    return flops / (chips * peak["flops_per_s"])
