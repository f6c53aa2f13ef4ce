"""Attention: GQA with full / sliding-window / local / bidirectional masks.

Backends:
  * ``reference`` — materializes the score matrix (small smoke tests, and
    the oracle for kernels/ref.py cross-checks).
  * ``chunked``  — streaming-softmax flash attention in pure JAX
    (lax.scan over KV chunks, fp32 accumulators). Memory-safe at 32k and
    the backend used by the multi-pod dry-run; structurally identical to
    the Pallas kernel.
  * ``pallas``   — the TPU kernel (kernels/flash_attention.py); validated
    on CPU via interpret=True.

Decode uses a positions-array cache that uniformly covers linear caches
(full attention) and ring buffers (sliding-window / local attention —
O(window) memory, which is what makes ``long_500k`` feasible for danube
and recurrentgemma).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.base import ParamSpec
from repro.models.layers import apply_rope

_NEG = -1.0e30


# --------------------------------------------------------------------------- #
# parameter specs
# --------------------------------------------------------------------------- #
def attn_specs(cfg) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # "fan_in" divides by shape[-2], but the fan-in of [d, H, hd] is d and
    # that of [H, hd, d] is H*hd: rescale to 1/sqrt(true fan-in). Without
    # it q.k/sqrt(hd) has std ~sqrt(d/H) and softmax becomes a near-argmax
    # that amplifies rounding differences layer after layer.
    q_in, o_in = math.sqrt(H / d), 1.0 / math.sqrt(H)
    specs = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim"),
                        init="fan_in", scale=q_in),
        "wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                        init="fan_in", scale=math.sqrt(KV / d)),
        "wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"),
                        init="fan_in", scale=math.sqrt(KV / d)),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"),
                        init="fan_in", scale=o_in),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
    return specs


# --------------------------------------------------------------------------- #
# masks
# --------------------------------------------------------------------------- #
def _mask(q_pos: jax.Array, kv_pos: jax.Array, mode: str,
          window: Optional[int]) -> jax.Array:
    """[S_q, S_k] boolean validity mask."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    if mode == "bidir":
        m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    else:
        m = qp >= kp
    if window is not None:
        m = m & (qp - kp < window)
    return m


# --------------------------------------------------------------------------- #
# full-sequence attention (train / prefill)
# --------------------------------------------------------------------------- #
def _reference_attention(q, k, v, mode, window):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qr = (q * (D ** -0.5)).reshape(B, S, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qr.astype(jnp.float32),
                   k.astype(jnp.float32))
    m = _mask(jnp.arange(S), jnp.arange(T), mode, window)
    s = jnp.where(m[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def _chunked_attention(q, k, v, mode, window, chunk):
    """Streaming-softmax (flash) attention via lax.scan over KV chunks."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    chunk = min(chunk, T)
    if T % chunk != 0:  # pad KV to a chunk multiple; padded keys are masked
        pad = chunk - T % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = k.shape[1]
    nc = Tp // chunk
    qr = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, S, KV, G, D)
    kc = jnp.moveaxis(k.reshape(B, nc, chunk, KV, D), 1, 0)  # [nc,B,c,KV,D]
    vc = jnp.moveaxis(v.reshape(B, nc, chunk, KV, D), 1, 0)
    q_pos = jnp.arange(S)

    def body(carry, xs):
        m, l, acc = carry
        ki, vi, ci = xs
        s = jnp.einsum("bskgd,bckd->bkgsc", qr, ki.astype(jnp.float32))
        kv_pos = ci * chunk + jnp.arange(chunk)
        valid = _mask(q_pos, kv_pos, mode, window) & (kv_pos < T)[None, :]
        s = jnp.where(valid[None, None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgsc,bckd->bkgsd", p, vi.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    a0 = jnp.zeros((B, KV, G, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, [1, 2], [2, 3]).reshape(B, S, H, D)
    return out.astype(q.dtype)


def multihead_attention(q, k, v, *, mode: str = "causal",
                        window: Optional[int] = None,
                        backend: str = "chunked", chunk: int = 1024):
    """q [B,S,H,D]; k,v [B,T,KV,D] with H % KV == 0 (GQA)."""
    if backend == "reference":
        return _reference_attention(q, k, v, mode, window)
    if backend == "chunked":
        return _chunked_attention(q, k, v, mode, window, chunk)
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, causal=(mode != "bidir"),
                                    window=window)
    raise ValueError(f"unknown attention backend {backend}")


# --------------------------------------------------------------------------- #
# block-level forward (projections + rope + attention)
# --------------------------------------------------------------------------- #
def attention_block(params: dict, cfg, sharder, x: jax.Array,
                    positions: jax.Array, *, mode: str,
                    window: Optional[int] = None) -> jax.Array:
    dt = x.dtype
    wq = sharder.gather(params["wq"].astype(dt), "embed", "heads", None)
    wk = sharder.gather(params["wk"].astype(dt), "embed", "kv_heads", None)
    wv = sharder.gather(params["wv"].astype(dt), "embed", "kv_heads", None)
    wo = sharder.gather(params["wo"].astype(dt), "heads", None, "embed")
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = sharder.constrain(q, "act_batch", None, "act_heads", None)
    k = sharder.constrain(k, "act_batch", None, "kv_heads", None)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    o = multihead_attention(
        q, k, v, mode=mode, window=window,
        backend=cfg.attn_backend, chunk=cfg.attn_chunk,
    )
    o = sharder.constrain(o, "act_batch", None, "act_heads", None)
    return jnp.einsum("bshk,hkd->bsd", o, wo)


# --------------------------------------------------------------------------- #
# decode (single new token against a cache)
# --------------------------------------------------------------------------- #
def cache_specs(cfg, batch: int, max_len: int, *, window: Optional[int]) -> dict:
    """Per-layer KV cache specs. ``window`` bounds the buffer (ring) for
    SWA/local attention; full attention stores max_len."""
    W = min(window, max_len) if window else max_len
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": ParamSpec((batch, W, KV, hd), ("kv_batch", "kv_seq", "kv_heads", None),
                       init="zeros", dtype=cfg.compute_dtype),
        "v": ParamSpec((batch, W, KV, hd), ("kv_batch", "kv_seq", "kv_heads", None),
                       init="zeros", dtype=cfg.compute_dtype),
        # absolute position stored in each slot; -1 = empty
        "pos": ParamSpec((batch, W), ("kv_batch", "kv_seq"),
                         init="const", scale=-1, dtype="int32"),
    }


def attention_decode(params: dict, cfg, sharder, x: jax.Array,
                     cache: dict, layer: jax.Array, positions: jax.Array, *,
                     window: Optional[int] = None) -> tuple[jax.Array, dict]:
    """x [B,1,d]; positions [B] absolute position of the new token (or
    [3,B] M-RoPE position streams for the VLM — the temporal stream [0]
    drives the cache slot and validity).

    ``cache`` holds every layer of the stack ([L,B,W,KV,hd] keys and
    values, [L,B,W] positions); this block's is index ``layer``. The new
    key, value and position land in place at ``[layer, b, pos % W]``
    (ring buffer; for full attention W is max_len so the ring is
    equivalent to a linear cache). The write is a select over the layer
    put back with a dynamic update, not a scatter: a scatter wants the
    head dim minor, and XLA would relayout the cache around it.
    """
    dt = x.dtype
    B = x.shape[0]
    W = cache["k"].shape[2]
    if positions.ndim == 2:  # [3, B] M-RoPE streams
        pos_t = positions[0]
        rope_pos = positions[:, :, None]  # [3,B,1]
    else:
        pos_t = positions
        rope_pos = positions[:, None]     # [B,1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = apply_rope(q, rope_pos, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, rope_pos, cfg.rope_theta, cfg.mrope_sections)

    positions = pos_t.astype(jnp.int32)
    hit = jnp.arange(W) == (positions % W)[:, None]  # [B, W]

    def put(buf, new):
        """Layer ``layer`` of ``buf`` with ``new`` [B,1,...] at its slot,
        and ``buf`` with that layer written back."""
        old = jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)
        sel = hit.reshape(hit.shape + (1,) * (old.ndim - 2))
        cur = jnp.where(sel, new.astype(buf.dtype), old)
        return cur, jax.lax.dynamic_update_index_in_dim(buf, cur, layer, 0)

    k_cache, k_all = put(cache["k"], k)
    v_cache, v_all = put(cache["v"], v)
    pos_cache, pos_all = put(cache["pos"], positions[:, None])

    D = q.shape[-1]
    KV = k_cache.shape[2]
    G = q.shape[2] // KV
    qr = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bwkd->bkgw", qr, k_cache.astype(jnp.float32))
    valid = (pos_cache >= 0) & (pos_cache <= positions[:, None])
    if window is not None:
        valid = valid & (positions[:, None] - pos_cache < window)
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, v_cache.astype(jnp.float32))
    o = o.reshape(B, 1, q.shape[2], D).astype(dt)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
    return y, {"k": k_all, "v": v_all, "pos": pos_all}
