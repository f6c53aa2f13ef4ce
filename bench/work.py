"""Operations and bytes of one call of the serve step, from the shapes of
a configuration file (``bench/configs/*.json``).

The step decodes one token for every row of its ``max_batch x max_len``
cache, whether the row is in use or not, and its attention reads every
cache position, valid or not. So one call:

- reads every weight at its stored dtype, except the embedding table, of
  which it gathers one row per batch row;
- reads the whole key and value cache and the cache's position table;
- does ``2 x max_batch`` operations per weight of its matrix products
  (the embedding is a gather, not a product), plus attention: ``q.k`` and
  ``p.v`` over all ``max_len`` positions for every head of every layer.

These are the least the step must move and compute; what it writes (one
cache slot per row) and its small element-wise work are left out, so the
roofline share they give cannot pass 100%. A step that stops reading the
whole shape (a ragged or paged cache) needs this count revisited first.
"""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def params(c: dict) -> dict:
    """Weight counts by part."""
    d, H, KV, hd, ff, V, L = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                              c["head_dim"], c["d_ff"], c["vocab"],
                              c["n_layers"])
    per_layer = (2 * d            # two norm gains
                 + d * H * hd * 2  # q and o projections
                 + d * KV * hd * 2  # k and v projections
                 + 3 * d * ff)     # gate, up, down
    return {"embed": V * d, "unembed": d * V, "final_norm": d,
            "layers": L * per_layer,
            "total": 2 * V * d + d + L * per_layer}


def serve_step(c: dict) -> dict:
    """``flops`` and ``bytes`` of one serve-step call."""
    p = params(c)
    B, T, L = c["max_batch"], c["max_len"], c["n_layers"]
    H, KV, hd, d = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_model"]
    W = min(c.get("swa_window") or T, T)
    wb = _BYTES[c["param_dtype"]]
    cb = _BYTES[c["compute_dtype"]]  # the cache is kept in compute dtype
    matmul_weights = p["total"] - p["embed"]
    weight_bytes = matmul_weights * wb + B * d * wb
    cache_bytes = L * (2 * B * W * KV * hd * cb + B * W * 4)
    attn_flops = L * B * 2 * (2 * H * hd * W)
    return {"flops": 2.0 * B * matmul_weights + attn_flops,
            "bytes": float(weight_bytes + cache_bytes),
            "weight_bytes": float(weight_bytes),
            "cache_bytes": float(cache_bytes)}
