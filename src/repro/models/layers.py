"""Shared layers: RMSNorm, embeddings, RoPE (incl. M-RoPE), MLPs."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.base import ParamSpec


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #
def rmsnorm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), ("embed",), init="ones")


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------- #
# embeddings
# --------------------------------------------------------------------------- #
def embed_specs(vocab: int, d: int) -> dict:
    return {
        "tok": ParamSpec((vocab, d), ("vocab", "embed"), init="embed", scale=0.02),
    }


def unembed_spec(d: int, vocab: int) -> ParamSpec:
    return ParamSpec((d, vocab), ("embed", "vocab"), init="fan_in")


def embed(tokens: jax.Array, tok_w: jax.Array, compute_dtype) -> jax.Array:
    # gather on a (vocab->model)-sharded table: GSPMD lowers to a masked
    # local gather + all-reduce
    return tok_w.astype(compute_dtype)[tokens]


def unembed(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))


# --------------------------------------------------------------------------- #
# RoPE (+ M-RoPE for qwen2-vl)
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original_max_position: int, beta_fast: float,
               beta_slow: float) -> jax.Array:
    """YaRN's frequencies (arXiv:2309.00071, as DeepSeek-V2 publishes
    them): each pair's frequency blends the original ``1/theta^(2i/D)``
    and the interpolated ``1/(factor theta^(2i/D))`` by a linear ramp
    between the pairs that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position``. [D/2]"""
    def pair(turns):  # the pair index that turns ``turns`` times
        return (head_dim * math.log(original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(
    x: jax.Array,              # [B, S, H, D]
    positions: jax.Array,      # [B, S] int32  or  [3, B, S] for M-RoPE
    theta: float,
    mrope_sections: Optional[tuple[int, ...]] = None,
    *,
    freqs: Optional[jax.Array] = None,
) -> jax.Array:
    """Rotary embedding, the halves of D rotated as pairs. With
    ``mrope_sections`` (in *pair* units summing to D/2), frequency bands
    are driven by the (temporal, h, w) position streams of qwen2-vl's
    multimodal RoPE. ``freqs`` [D/2] replaces ``theta``'s (YaRN)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)  # [D/2]
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[0]
        angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    else:
        assert positions.ndim == 3, "M-RoPE needs [3, B, S] positions"
        assert sum(mrope_sections) == d // 2, (mrope_sections, d)
        sect_pos = []
        for i, n in enumerate(mrope_sections):
            sect_pos.append(
                jnp.broadcast_to(
                    positions[i][..., None].astype(jnp.float32),
                    positions.shape[1:] + (n,),
                )
            )
        pos_per_freq = jnp.concatenate(sect_pos, axis=-1)  # [B,S,D/2]
        angles = pos_per_freq * freqs
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #
def mlp_specs(d: int, ff: int, act: str) -> dict:
    if act == "silu":  # SwiGLU: gate+up+down
        return {
            "gate": ParamSpec((d, ff), ("embed", "mlp"), init="fan_in"),
            "up": ParamSpec((d, ff), ("embed", "mlp"), init="fan_in"),
            "down": ParamSpec((ff, d), ("mlp", "embed"), init="fan_in"),
        }
    # classic 2-matrix GeLU FFN (hubert)
    return {
        "w1": ParamSpec((d, ff), ("embed", "mlp"), init="fan_in"),
        "b1": ParamSpec((ff,), ("mlp",), init="zeros"),
        "w2": ParamSpec((ff, d), ("mlp", "embed"), init="fan_in"),
        "b2": ParamSpec((d,), ("embed",), init="zeros"),
    }


def mlp(params: dict, x: jax.Array, act: str, sharder=None) -> jax.Array:
    dt = x.dtype

    def g_(w, *axes):  # FSDP use-time gather (no-op unless enabled)
        w = w.astype(dt)
        return sharder.gather(w, *axes) if sharder is not None else w

    if act == "silu":
        g = jnp.einsum("bsd,df->bsf", x, g_(params["gate"], "embed", "mlp"))
        u = jnp.einsum("bsd,df->bsf", x, g_(params["up"], "embed", "mlp"))
        h = jax.nn.silu(g) * u
        if sharder is not None:
            h = sharder.constrain(h, "act_batch", None, "act_mlp")
        return jnp.einsum("bsf,fd->bsd", h, g_(params["down"], "mlp", "embed"))
    h = jnp.einsum("bsd,df->bsf", x, g_(params["w1"], "embed", "mlp"))
    h = jax.nn.gelu(h + params["b1"].astype(dt))
    if sharder is not None:
        h = sharder.constrain(h, "act_batch", None, "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, g_(params["w2"], "mlp", "embed")) \
        + params["b2"].astype(dt)


# --------------------------------------------------------------------------- #
# modality frontends (stubs per assignment: precomputed patch/frame embeds)
# --------------------------------------------------------------------------- #
def frontend_proj_spec(d_in: int, d: int) -> ParamSpec:
    return ParamSpec((d_in, d), ("embed", None), init="fan_in")


def frontend_proj(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("bsi,id->bsd", x, w.astype(x.dtype))
