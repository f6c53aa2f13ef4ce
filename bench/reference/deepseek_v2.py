"""The DeepSeek-V2 family: multi-head latent attention and a mixture of
experts with shared experts, served from one chip's share of the experts.
Everything the benchmark knows of it, for ``deepseek_v2_lite``.

It exports what the harness takes by the configuration's ``reference``
key (``bench/reference/llama_dense.py`` says what each is): ``served``,
``serve_step``, ``gaps`` and ``small``.

The reference is the forward pass of arXiv:2405.04434 and the published
modeling code (``modeling_deepseek.py``), in float32 at
``Precision.HIGHEST``, over whole sequences, with no cache, no absorption
and no batching tricks:

    x = E[tokens]
    per layer:  h = rmsnorm(x) * g1
                q = h Wq                          [H, nope + rope]
                c, k_pe = split(h Wkv_a)          [kv_lora_rank], [rope]
                k_nope, v = split(rmsnorm(c) * g_kv  Wkv_b)   per head
                rotate q's rope part and k_pe by position (YaRN)
                k = [k_nope, k_pe] (one k_pe shared by every head)
                a = softmax(q k^T * scale + causal) v
                x = x + a Wo
                h = rmsnorm(x) * g2
                layer 0:  x = x + (silu(h Wgate) * (h Wup)) Wdown
                else:     p = softmax(h Wrouter)  over all n_experts
                          the top_k of p, greedy, gates not renormalised
                          x = x + sum over picks of a held expert e of
                                  p_e * expert_e(h)  + shared(h)
    logits = (rmsnorm(x) * g) U

Conventions, each as published unless noted:
- YaRN frequencies: each rope pair's frequency blends 1/theta^(2i/D) and
  1/(factor theta^(2i/D)) by the linear ramp between the pairs that turn
  beta_fast and beta_slow times over original_max_position_embeddings;
  cos and sin are scaled by mscale(factor, mscale) / mscale(factor,
  mscale_all_dim) (1 here), the scores by mscale(factor,
  mscale_all_dim)^2 / sqrt(nope + rope), mscale(f, m) = 0.1 m ln f + 1.
- Rope pairs are the two halves of the 64 rope dims, where the published
  code pairs interleaved dims: with seeded weights that is a fixed
  permutation of the rope columns of Wq and Wkv_a, so the same model.
- The expert share: the router keeps all its outputs and the chip holds
  experts 0 .. n_held-1; a pick of any other expert adds nothing here (it
  would be added on the chip that holds it). Shared experts run whole.
- Shared experts are one SwiGLU of width n_shared * expert width.

It imports nothing of the system under test and makes its own weights
from the seed, one layer at a time (``bench/weights.py``'s discipline:
leaf ``n`` of layer ``l`` from ``fold_in(fold_in(base, id(n)), l)``, the
dense layer 0 and the expert layers 1.. on one count).

``control=True`` runs, beside it, the same model with every linear layer
(the router's among them) in float8 e4m3, per-tensor weight and per-row
activation scales: the precision step below the served bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench import work

ATTN = ("ln1", "ln2", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")
DENSE = ("gate", "up", "down")
MOE = ("router", "wg", "wu", "wd", "s_gate", "s_up", "s_down")
TOP = ("embed", "final_norm", "unembed")
#: a leaf's id, the number folded into the base key
IDS = ATTN + DENSE + MOE + TOP

#: each leaf's path in the served tree under its stack
PATHS = {
    "ln1": ("ln1",), "ln2": ("ln2",), "wq": ("attn", "wq"),
    "wkv_a": ("attn", "wkv_a"), "kv_a_norm": ("attn", "kv_a_norm"),
    "wkv_b": ("attn", "wkv_b"), "wo": ("attn", "wo"),
    "gate": ("mlp", "gate"), "up": ("mlp", "up"), "down": ("mlp", "down"),
    "router": ("moe", "router"), "wg": ("moe", "wg"), "wu": ("moe", "wu"),
    "wd": ("moe", "wd"), "s_gate": ("moe", "shared", "gate"),
    "s_up": ("moe", "shared", "up"), "s_down": ("moe", "shared", "down"),
    "embed": ("embed", "tok"), "final_norm": ("final_norm",),
    "unembed": ("unembed",),
}
#: leaves kept in float32 whatever the served dtype (the program's router)
F32 = ("router",)


def _shapes(c: dict) -> dict:
    """Each leaf's (shape, fan) as ``bench.weights.leaf`` takes it."""
    d, H, V = c["d_model"], c["n_heads"], c["vocab"]
    C, N, R, Vh = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"], c["v_head_dim"])
    E, held, ff = c["n_experts"], c["experts_held"], c["expert_d_ff"]
    sff = c["n_shared_experts"] * ff
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, H, N + R), d), "wkv_a": ((d, C + R), d),
        "kv_a_norm": ((C,), None), "wkv_b": ((C, H, N + Vh), C),
        "wo": ((H, Vh, d), H * Vh),
        "gate": ((d, c["d_ff"]), d), "up": ((d, c["d_ff"]), d),
        "down": ((c["d_ff"], d), c["d_ff"]),
        "router": ((d, E), d), "wg": ((held, d, ff), d),
        "wu": ((held, d, ff), d), "wd": ((held, ff, d), ff),
        "s_gate": ((d, sff), d), "s_up": ((d, sff), d),
        "s_down": ((sff, d), sff),
        "embed": ((V, d), "embed"), "final_norm": ((d,), None),
        "unembed": ((d, V), d),
    }


def _leaves(c: dict, base, l, names, dtype) -> dict:
    sh = _shapes(c)
    return {n: W.leaf(jax.random.fold_in(
                jax.random.fold_in(base, IDS.index(n)), l),
                *sh[n], jnp.float32 if n in F32 else dtype)
            for n in names}


def top(c: dict, base, dtype) -> dict:
    return {n: W.leaf(jax.random.fold_in(base, IDS.index(n)),
                      *_shapes(c)[n], dtype) for n in TOP}


def _nest(out: dict, flat: dict, stack: tuple = ()) -> dict:
    for name, x in flat.items():
        *head, last = stack + PATHS[name]
        d = out
        for h in head:
            d = d.setdefault(h, {})
        d[last] = x
    return out


def served(c: dict, seed: int, server: int, dtype) -> dict:
    """All weights of one server in the served tree (the dense layers
    stacked under ``dense_layers``, the expert layers under ``layers``),
    made in one jitted call."""
    k, L = c["first_k_dense"], c["n_layers"]

    @jax.jit
    def make(base):
        dense = jax.vmap(lambda l: _leaves(c, base, l, ATTN + DENSE, dtype))(
            jnp.arange(k))
        moe = jax.vmap(lambda l: _leaves(c, base, l, ATTN + MOE, dtype))(
            jnp.arange(k, L))
        out = _nest({}, top(c, base, dtype))
        _nest(out, dense, ("dense_layers",))
        return _nest(out, moe, ("layers",))

    return make(W.base_key(seed, server))


def small(c: dict, *, full_width: bool = False) -> dict:
    """The dense layer and one expert layer and, unless ``full_width``,
    tiny widths with the share's proportions (8 of 64 experts held)."""
    c = dict(c, n_layers=2)
    if not full_width:
        c.update(d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, d_ff=128, expert_d_ff=32, vocab=512)
    return c


def params(c: dict) -> dict:
    """Weight counts by part."""
    sh = _shapes(c)
    n = {k: math.prod(s) for k, (s, _) in sh.items()}
    k, L = c["first_k_dense"], c["n_layers"]
    attn = sum(n[x] for x in ATTN)
    dense = attn + sum(n[x] for x in DENSE)
    experts = n["wg"] + n["wu"] + n["wd"]
    shared = n["s_gate"] + n["s_up"] + n["s_down"]
    moe = attn + n["router"] + experts + shared
    return {"embed": n["embed"], "unembed": n["unembed"],
            "final_norm": n["final_norm"], "attn_layer": attn,
            "experts": (L - k) * experts, "shared": (L - k) * shared,
            "router": (L - k) * n["router"], "attn": L * attn,
            "total": n["embed"] + n["unembed"] + n["final_norm"]
            + k * dense + (L - k) * moe}


def serve_step(c: dict) -> dict:
    """One call decodes a token for every row of its ``max_batch x
    max_len`` latent cache, in use or not. Bytes: every weight but the
    embedding table, of which it gathers one row per batch row, at its
    stored dtype (the router in float32); the whole latent cache
    (``kv_lora_rank + rope`` per token and layer) and its position table.
    Operations: ``2 x max_batch`` per dense, attention, router and shared
    weight (``Wkv_b`` among them: the absorbed ``q_nope W_UK`` and ``o
    W_UV`` are its two halves); each held expert's weights at ``max_batch
    x top_k / n_experts`` picks; the absorbed ``q.c`` over ``kv_lora_rank
    + rope`` and ``p.c`` over ``kv_lora_rank`` for every head, position
    and layer.

    Beside the four keys: ``latent_cache_bytes``, ``expert_bytes`` (held
    routed experts), and for the expert metrics ``expert_layers`` and
    ``experts_held``."""
    p = params(c)
    B, T, L = c["max_batch"], c["max_len"], c["n_layers"]
    H, d = c["n_heads"], c["d_model"]
    C, R = c["kv_lora_rank"], c["qk_rope_head_dim"]
    E, K, held = c["n_experts"], c["top_k"], c["experts_held"]
    wb = work.BYTES[c["param_dtype"]]
    cb = work.BYTES[c["compute_dtype"]]  # the cache is kept in compute dtype
    matmul = p["total"] - p["embed"]
    weight_bytes = ((matmul - p["router"]) * wb + p["router"] * 4
                    + B * d * wb)
    latent = L * B * T * (C + R) * cb
    cache_bytes = latent + L * B * T * 4
    picks = B * K / E  # per held expert, on average
    dense_flops = 2.0 * B * (matmul - p["experts"])
    expert_flops = 2.0 * picks * p["experts"]
    attn_flops = L * B * H * (2 * T * (C + R) + 2 * T * C)
    return {"flops": dense_flops + expert_flops + attn_flops,
            "bytes": float(weight_bytes + cache_bytes),
            "weight_bytes": float(weight_bytes),
            "cache_bytes": float(cache_bytes),
            "latent_cache_bytes": float(latent),
            "expert_bytes": float(p["experts"] * wb),
            "expert_layers": L - c["first_k_dense"], "experts_held": held}


HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _lin(spec, x, w, fp8: bool):
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, tuple(range(w.ndim)))
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mscale(f, m):
    return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0


def _yarn(c: dict):
    """(inverse frequencies [rope/2], cos/sin amplitude, score scale)."""
    D, base = c["qk_rope_head_dim"], c["rope_theta"]
    f = c["rope_scaling_factor"]
    orig = c["rope_original_max_position"]

    def dim(rot):
        return D * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim(c["yarn_beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, D, 2, dtype=np.float64) / D)
    inter = 1.0 / (f * base ** (np.arange(0, D, 2, dtype=np.float64) / D))
    mask = 1.0 - np.clip((np.arange(D // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    m_all = _mscale(f, c["yarn_mscale_all_dim"])
    amp = _mscale(f, c["yarn_mscale"]) / m_all
    scale = (c["qk_nope_head_dim"] + D) ** -0.5 * m_all ** 2
    return jnp.asarray(inv, jnp.float32), amp, scale


def _rope(x, inv, amp):
    # x [N, T, H, D]; position = index along T; halves rotated
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = (jnp.cos(ang) * amp)[None, :, None]
    sin = (jnp.sin(ang) * amp)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, g, u, dn, fp8):
    return _lin("ntf,fd->ntd", jax.nn.silu(_lin("ntd,df->ntf", x, g, fp8))
                * _lin("ntd,df->ntf", x, u, fp8), dn, fp8)


def _attention(c: dict, x, w: dict, fp8: bool):
    H, C, N = c["n_heads"], c["kv_lora_rank"], c["qk_nope_head_dim"]
    T = x.shape[1]
    inv, amp, scale = _yarn(c)
    q = _lin("ntd,dhk->nthk", x, w["wq"], fp8)
    ckv = _lin("ntd,dk->ntk", x, w["wkv_a"], fp8)
    kv = _lin("ntc,chk->nthk", _rms(ckv[..., :C], w["kv_a_norm"],
                                    c["norm_eps"]), w["wkv_b"], fp8)
    k_pe = _rope(ckv[:, :, None, C:], inv, amp)     # one key for all heads
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], inv, amp)], -1)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe, kv.shape[:3] + k_pe.shape[-1:])],
        -1)
    s = jnp.einsum("nthk,nshk->nhts", q, k, precision=HI) * scale
    i = jnp.arange(T)
    s = jnp.where(i[:, None] >= i[None, :], s, -jnp.inf)
    a = jnp.einsum("nhts,nshk->nthk", jax.nn.softmax(s, -1), kv[..., N:],
                   precision=HI)
    return _lin("nthk,hkd->ntd", a, w["wo"], fp8)


def _experts(c: dict, x, w: dict, fp8: bool):
    """Routed experts of the share, then the shared experts."""
    K, held = c["top_k"], c["experts_held"]
    p = jax.nn.softmax(_lin("ntd,de->nte", x, w["router"], fp8), -1)
    top, idx = jax.lax.top_k(p, K)                      # greedy
    if c["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    y = _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], fp8)
    for e in range(held):
        gate = jnp.where(idx == e, top, 0.0).sum(-1)    # [N, T]
        y = y + gate[..., None] * _swiglu(x, w["wg"][e], w["wu"][e],
                                          w["wd"][e], fp8)
    return y


def _block(c: dict, x, w: dict, fp8: bool, moe: bool):
    x = x + _attention(c, _rms(x, w["ln1"], c["norm_eps"]), w, fp8)
    h = _rms(x, w["ln2"], c["norm_eps"])
    if moe:
        return x + _experts(c, h, w, fp8)
    return x + _swiglu(h, w["gate"], w["up"], w["down"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(cfg_items, base, l, xs, dtype, moe):
    c = dict(cfg_items)
    w = {n: a.astype(jnp.float32) for n, a in _leaves(
        c, base, l, ATTN + (MOE if moe else DENSE), dtype).items()}
    return tuple(_block(c, x, w, fp8, moe) for x, fp8 in zip(xs, (False, True)))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_items, base, tokens, dtype):
    c = dict(cfg_items)
    return top(c, base, dtype)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _head(cfg_items, base, x_ref, x_ctl, nxt, dtype, control):
    """Per position of one sequence: the reference's best logit minus its
    logit of the next token, and, with the control, minus its logit of
    the token the control puts first."""
    c = dict(cfg_items)
    t = top(c, base, dtype)
    g = t["final_norm"].astype(jnp.float32)
    u = t["unembed"].astype(jnp.float32)
    lg = jnp.einsum("td,dv->tv", _rms(x_ref, g, c["norm_eps"]), u,
                    precision=HI)
    best = lg.max(-1)
    served_gap = best - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
    if not control:
        return served_gap, served_gap
    lc = _lin("td,dv->tv", _rms(x_ctl, g, c["norm_eps"]), u, True)
    pick = jnp.argmax(lc, -1)
    return served_gap, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]


def gaps(c: dict, seed: int, server: int, seqs: np.ndarray, *,
         control: bool = False):
    """``seqs`` [N, T] int32 (prompt then served tokens, zero-padded).

    Returns ``served`` [N, T-1]: at position t, by how much the
    reference's logit of ``seqs[:, t+1]`` lies below its best; and
    ``ctl`` [N, T-1] (None without ``control``): the same for the token
    the float8 control puts first at t."""
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str, type(None)))))
    dtype = jnp.dtype(c["param_dtype"])
    base = W.base_key(seed, server)
    x = _embed(items, base, jnp.asarray(seqs, jnp.int32), dtype)
    xs = (x, x) if control else (x,)
    for l in range(c["n_layers"]):
        xs = _layer(items, base, l, xs, dtype, l >= c["first_k_dense"])
    nxt = jnp.asarray(np.concatenate(
        [seqs[:, 1:], np.zeros_like(seqs[:, :1])], 1), jnp.int32)
    served_gaps, ctl = [], []
    for n in range(seqs.shape[0]):
        s, k = _head(items, base, xs[0][n], xs[-1][n], nxt[n], dtype,
                     control)
        served_gaps.append(np.asarray(s)[:-1])
        ctl.append(np.asarray(k)[:-1])
    return np.stack(served_gaps), (np.stack(ctl) if control else None)
