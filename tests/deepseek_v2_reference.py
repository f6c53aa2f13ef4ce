"""A plain DeepSeek-V2 forward pass (arXiv:2405.04434; the published
``modeling_deepseek.py``), for the tests: float32 at ``Precision.HIGHEST``,
whole sequences, no cache, no absorption, no batching tricks. It imports
nothing of the program; it reads a parameter tree laid out as the
program's (``embed.tok``, ``dense_layers``, ``layers``, ``final_norm``,
``unembed``) and a dict of the configuration's sizes.

Conventions, each as published unless noted:

- Attention: ``q = h Wq`` per head, ``[c, k_pe] = h Wkv_a``;
  ``[k_nope, v] = rmsnorm(c) Wkv_b`` per head; the rope parts of q and the
  one ``k_pe`` that every head shares are rotated by position; scores
  ``q . [k_nope, k_pe]`` times ``mscale(f, mscale_all_dim)^2 /
  sqrt(nope + rope)``, causal softmax, ``o = p v``, then ``Wo``.
- YaRN: pair i's frequency is ``extra_i (1 - r_i) + extra_i / f r_i``,
  ``extra_i = theta^(-2i/D)``, ``r`` the linear ramp from
  ``floor(dim(beta_fast))`` to ``ceil(dim(beta_slow))``, ``dim(n) = D
  ln(orig / (2 pi n)) / (2 ln theta)``; cos and sin times
  ``mscale(f, mscale) / mscale(f, mscale_all_dim)``; ``mscale(f, m) = 0.1
  m ln f + 1``.
- Rope pairs are the halves of the rope dims; the published code pairs
  interleaved dims. With seeded weights that is a fixed permutation of
  the rope columns of Wq and Wkv_a: the same model.
- Experts: ``p = softmax(h Wrouter)`` over all ``n_experts``, the greedy
  top-k, renormalised only where ``norm_topk_prob``; each pick of an
  expert held here (ids ``first .. first + held - 1``, ``held`` the
  leading dim of ``wg``) adds ``p_e`` times its SwiGLU; a pick of any other
  expert adds nothing. The shared experts are one SwiGLU over every token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mscale(f: float, m: float) -> float:
    return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0


def yarn(c: dict):
    """(inverse frequencies [D/2] as float64, cos/sin amplitude, score
    scale)."""
    D, theta = c["qk_rope_head_dim"], c["rope_theta"]
    f, orig = c["rope_scaling_factor"], c["rope_original_max_position"]
    extra = theta ** (-np.arange(0, D, 2) / D)
    scale = (c["qk_nope_head_dim"] + D) ** -0.5
    if not f:
        return extra, 1.0, scale

    def dim(n):
        return D * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))

    lo = max(math.floor(dim(c["yarn_beta_fast"])), 0)
    hi = min(math.ceil(dim(c["yarn_beta_slow"])), D - 1)
    if lo == hi:
        hi += 0.001
    r = np.clip((np.arange(D // 2) - lo) / (hi - lo), 0.0, 1.0)
    inv = extra * (1 - r) + extra / f * r
    m_all = mscale(f, c["yarn_mscale_all_dim"])
    return inv, mscale(f, c["yarn_mscale"]) / m_all, scale * m_all ** 2


def rope(x, inv, amp):
    """x [N, T, H, D], rotated by position (the index along T)."""
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * amp, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * amp, jnp.float32)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: dict, x, w: dict):
    C, N = c["kv_lora_rank"], c["qk_nope_head_dim"]
    inv, amp, scale = yarn(c)
    q = _mm("ntd,dhk->nthk", x, w["wq"])
    ckv = _mm("ntd,dk->ntk", x, w["wkv_a"])
    kv = _mm("ntc,chk->nthk", rms(ckv[..., :C], w["kv_a_norm"],
                                  c["norm_eps"]), w["wkv_b"])
    k_pe = rope(ckv[:, :, None, C:], inv, amp)
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], inv, amp)], -1)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        k_pe, kv.shape[:3] + k_pe.shape[-1:])], -1)
    s = _mm("nthk,nshk->nhts", q, k) * scale
    T = x.shape[1]
    s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
    o = _mm("nhts,nshk->nthk", jax.nn.softmax(s, -1), kv[..., N:])
    return _mm("nthk,hkd->ntd", o, w["wo"])


def swiglu(x, g, u, d):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, g))
               * _mm("...d,df->...f", x, u), d)


def experts(c: dict, x, w: dict, *, first: int = 0, shared: bool = True):
    """The routed experts held here, and the shared experts unless
    ``shared`` is False. x [..., d]."""
    p = jax.nn.softmax(_mm("...d,de->...e", x, w["router"]), -1)
    top, idx = jax.lax.top_k(p, c["top_k"])
    if c["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    if shared and c["n_shared_experts"]:
        s = w["shared"]
        y = swiglu(x, s["gate"], s["up"], s["down"])
    for e in range(w["wg"].shape[0]):
        gate = jnp.where(idx == first + e, top, 0.0).sum(-1)
        y = y + gate[..., None] * swiglu(x, w["wg"][e], w["wu"][e],
                                         w["wd"][e])
    return y


def block(c: dict, x, w: dict, *, first: int = 0):
    x = x + attention(c, rms(x, w["ln1"], c["norm_eps"]), w["attn"])
    h = rms(x, w["ln2"], c["norm_eps"])
    if "mlp" in w:
        m = w["mlp"]
        return x + swiglu(h, m["gate"], m["up"], m["down"])
    return x + experts(c, h, w["moe"], first=first)


def forward(c: dict, params: dict, tokens, *, first: int = 0):
    """tokens [N, T] -> logits [N, T, V], float32."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 params)
    x = f32["embed"]["tok"][jnp.asarray(tokens)]
    for stack in ("dense_layers", "layers"):
        n = f32[stack]["ln1"].shape[0]
        for l in range(n):
            x = block(c, x, jax.tree_util.tree_map(lambda a: a[l],
                                                   f32[stack]), first=first)
    return _mm("ntd,dv->ntv", rms(x, f32["final_norm"], c["norm_eps"]),
               f32["unembed"])
