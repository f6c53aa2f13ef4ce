"""Attention: GQA with full / sliding-window / local / bidirectional masks,
and multi-head latent attention (MLA, deepseek-v2: ``cfg.kv_lora_rank``).

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
from repro.models.layers import apply_rope, rmsnorm, yarn_freqs, yarn_mscale

_NEG = -1.0e30


# --------------------------------------------------------------------------- #
# parameter specs
# --------------------------------------------------------------------------- #
def attn_specs(cfg) -> dict:
    if cfg.kv_lora_rank:
        return mla_specs(cfg)
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
def _reference_attention(q, k, v, mode, window, scale):
    B, S, H, D = q.shape
    T, KV, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    qr = (q * scale).reshape(B, S, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qr.astype(jnp.float32),
                   k.astype(jnp.float32))
    m = _mask(jnp.arange(S), jnp.arange(T), mode, window)
    s = jnp.where(m[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, Dv).astype(q.dtype)


def _chunked_attention(q, k, v, mode, window, chunk, scale):
    """Streaming-softmax (flash) attention via lax.scan over KV chunks."""
    B, S, H, D = q.shape
    T, KV, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    chunk = min(chunk, T)
    if T % chunk != 0:  # pad KV to a chunk multiple; padded keys are masked
        pad = chunk - T % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = k.shape[1]
    nc = Tp // chunk
    qr = (q.astype(jnp.float32) * scale).reshape(B, S, KV, G, D)
    kc = jnp.moveaxis(k.reshape(B, nc, chunk, KV, D), 1, 0)  # [nc,B,c,KV,D]
    vc = jnp.moveaxis(v.reshape(B, nc, chunk, KV, Dv), 1, 0)
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
    a0 = jnp.zeros((B, KV, G, S, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, [1, 2], [2, 3]).reshape(B, S, H, Dv)
    return out.astype(q.dtype)


def multihead_attention(q, k, v, *, mode: str = "causal",
                        window: Optional[int] = None,
                        backend: str = "chunked", chunk: int = 1024,
                        scale: Optional[float] = None):
    """q, k [B,S|T,H|KV,D]; v [B,T,KV,Dv] with H % KV == 0 (GQA). Scores
    are scaled by ``scale``, or 1/sqrt(D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if backend == "reference":
        return _reference_attention(q, k, v, mode, window, scale)
    if backend == "chunked":
        return _chunked_attention(q, k, v, mode, window, chunk, scale)
    if backend == "pallas":
        if scale != q.shape[-1] ** -0.5 or v.shape[-1] != q.shape[-1]:
            raise ValueError("the flash kernel takes 1/sqrt(D) and Dv == D")
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
    if cfg.kv_lora_rank:
        return mla_block(params, cfg, sharder, x, positions, mode=mode,
                         window=window)
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
    if cfg.kv_lora_rank:
        return mla_cache_specs(cfg, batch, W)
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


def _put(buf, new, layer, hit):
    """Layer ``layer`` of the stacked ``buf`` [L,B,W,...] with ``new``
    [B,1,...] at each row's slot (``hit`` [B,W]), and ``buf`` with that
    layer written back: a select and one dynamic update, in place where
    ``buf`` is donated."""
    old = jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)
    sel = hit.reshape(hit.shape + (1,) * (old.ndim - 2))
    cur = jnp.where(sel, new.astype(buf.dtype), old)
    return cur, jax.lax.dynamic_update_index_in_dim(buf, cur, layer, 0)


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
    if cfg.kv_lora_rank:
        return mla_decode(params, cfg, x, cache, layer, positions)
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
    k_cache, k_all = _put(cache["k"], k, layer, hit)
    v_cache, v_all = _put(cache["v"], v, layer, hit)
    pos_cache, pos_all = _put(cache["pos"], positions[:, None], layer, hit)

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


# --------------------------------------------------------------------------- #
# multi-head latent attention (MLA, deepseek-v2; arXiv:2405.04434 §2.1)
# --------------------------------------------------------------------------- #
def mla_specs(cfg) -> dict:
    """No query compression (deepseek-v2-lite): ``wq`` [d,H,nope+rope];
    ``wkv_a`` [d, kv_lora_rank + rope] (the latent and the one rope key
    shared by every head); ``kv_a_norm`` on the latent; ``wkv_b``
    [kv_lora_rank, H, nope + v] (W_UK then W_UV); ``wo`` [H, v, d]."""
    d, H, C = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, H, N + R), ("embed", "heads", "head_dim"),
                        init="fan_in", scale=math.sqrt(H / d)),
        "wkv_a": ParamSpec((d, C + R), ("embed", None), init="fan_in"),
        "kv_a_norm": ParamSpec((C,), (None,), init="ones"),
        "wkv_b": ParamSpec((C, H, N + V), (None, "heads", "head_dim"),
                           init="fan_in", scale=math.sqrt(H / C)),
        "wo": ParamSpec((H, V, d), ("heads", "head_dim", "embed"),
                        init="fan_in", scale=1.0 / math.sqrt(H)),
    }


def mla_cache_specs(cfg, batch: int, W: int) -> dict:
    """Per layer, the normed latent ``c_kv`` [B,W,kv_lora_rank] and the
    roped shared key ``k_pe`` [B,W,rope]: kv_lora_rank + rope values per
    token and layer, whatever the head count."""
    axes = ("kv_batch", "kv_seq", None)
    return {
        "c_kv": ParamSpec((batch, W, cfg.kv_lora_rank), axes, init="zeros",
                          dtype=cfg.compute_dtype),
        "k_pe": ParamSpec((batch, W, cfg.qk_rope_head_dim), axes,
                          init="zeros", dtype=cfg.compute_dtype),
        "pos": ParamSpec((batch, W), ("kv_batch", "kv_seq"),
                         init="const", scale=-1, dtype="int32"),
    }


def _mla_rope(cfg):
    """(frequencies [rope/2], rope amplitude, softmax scale). YaRN scales
    cos/sin by mscale(factor, yarn_mscale) / mscale(factor,
    yarn_mscale_all_dim) and the scores by mscale(factor,
    yarn_mscale_all_dim)^2 / sqrt(nope + rope)."""
    R = cfg.qk_rope_head_dim
    scale = (cfg.qk_nope_head_dim + R) ** -0.5
    if not cfg.rope_scaling_factor:
        return None, 1.0, scale
    f = cfg.rope_scaling_factor
    freqs = yarn_freqs(R, cfg.rope_theta, f, cfg.rope_original_max_position,
                       cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    m_all = yarn_mscale(f, cfg.yarn_mscale_all_dim)
    return freqs, yarn_mscale(f, cfg.yarn_mscale) / m_all, scale * m_all ** 2


def _mla_project(params, cfg, x, positions):
    """x [B,S,d] -> q_nope [B,S,H,nope], q_pe [B,S,H,rope], the normed
    latent c [B,S,C] and the shared k_pe [B,S,rope], roped."""
    dt = x.dtype
    C, N = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    freqs, amp, _ = _mla_rope(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    kv = jnp.einsum("bsd,dk->bsk", x, params["wkv_a"].astype(dt))
    c = rmsnorm(kv[..., :C], params["kv_a_norm"], cfg.norm_eps)
    q_pe = apply_rope(q[..., N:], positions, cfg.rope_theta, freqs=freqs)
    k_pe = apply_rope(kv[:, :, None, C:], positions, cfg.rope_theta,
                      freqs=freqs)[:, :, 0]
    if amp != 1.0:
        q_pe, k_pe = q_pe * amp, k_pe * amp
    return q[..., :N], q_pe, c, k_pe


def mla_block(params: dict, cfg, sharder, x: jax.Array, positions, *,
              mode: str, window: Optional[int] = None) -> jax.Array:
    """Full-sequence MLA, not absorbed: keys and values decompressed from
    the latent, the shared rope key broadcast over the heads."""
    dt = x.dtype
    B, S, _ = x.shape
    H, N = cfg.n_heads, cfg.qk_nope_head_dim
    q_nope, q_pe, c, k_pe = _mla_project(params, cfg, x, positions)
    kv = jnp.einsum("bsc,chk->bshk", c, params["wkv_b"].astype(dt))
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe[:, :, None], (B, S, H,
                                                          k_pe.shape[-1]))],
        -1)
    q = sharder.constrain(q, "act_batch", None, "act_heads", None)
    o = multihead_attention(q, k, kv[..., N:], mode=mode, window=window,
                            backend=cfg.attn_backend, chunk=cfg.attn_chunk,
                            scale=_mla_rope(cfg)[2])
    o = sharder.constrain(o, "act_batch", None, "act_heads", None)
    return jnp.einsum("bshv,hvd->bsd", o, params["wo"].astype(dt))


def mla_decode(params: dict, cfg, x: jax.Array, cache: dict,
               layer: jax.Array, positions: jax.Array
               ) -> tuple[jax.Array, dict]:
    """One token per row against the latent cache, with W_UK absorbed into
    the query and W_UV applied after the weighted sum:

        q_lat = q_nope W_UK                          [B,H,C]
        s     = (q_lat . c_kv + q_pe . k_pe) * scale [B,H,W]
        o     = (softmax(s) . c_kv) W_UV             [B,H,v]

    ``cache`` is the whole stack ([L,B,W,C] ``c_kv``, [L,B,W,rope]
    ``k_pe``, [L,B,W] ``pos``); the new slot of layer ``layer`` lands in
    place as ``attention_decode``'s does."""
    dt = x.dtype
    f32 = jnp.float32
    W = cache["c_kv"].shape[2]
    N = cfg.qk_nope_head_dim
    positions = positions.astype(jnp.int32)
    with jax.named_scope("mla.project"):
        q_nope, q_pe, c, k_pe = _mla_project(params, cfg, x,
                                             positions[:, None])
    with jax.named_scope("mla.cache_write"):
        hit = jnp.arange(W) == (positions % W)[:, None]  # [B, W]
        c_cache, c_all = _put(cache["c_kv"], c, layer, hit)
        r_cache, r_all = _put(cache["k_pe"], k_pe, layer, hit)
        pos_cache, pos_all = _put(cache["pos"], positions[:, None], layer,
                                  hit)
    with jax.named_scope("mla.attend"):
        wkv_b = params["wkv_b"].astype(f32)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(f32),
                           wkv_b[..., :N])
        s = (jnp.einsum("bhc,bwc->bhw", q_lat, c_cache.astype(f32))
             + jnp.einsum("bhr,bwr->bhw", q_pe[:, 0].astype(f32),
                          r_cache.astype(f32))) * _mla_rope(cfg)[2]
        valid = (pos_cache >= 0) & (pos_cache <= positions[:, None])
        s = jnp.where(valid[:, None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhw,bwc->bhc", p, c_cache.astype(f32))
        o = jnp.einsum("bhc,chv->bhv", o_lat, wkv_b[..., N:]).astype(dt)
        y = jnp.einsum("bhv,hvd->bd", o, params["wo"].astype(dt))[:, None]
    return y, {"c_kv": c_all, "k_pe": r_all, "pos": pos_all}
