"""The dense decoder family with grouped-query attention: everything the
benchmark knows of it, for ``smollm_360m`` and ``h2o_danube3_4b``.

A family's module exports what the harness takes by the configuration's
``reference`` key:

- ``served(conf, seed, server, dtype)``: one server's weights from the
  seed, in the served tree (``TREE``, the seed discipline of
  ``bench.weights``);
- ``serve_step(conf)``: the least work of one serve-step call
  (``bench.work``);
- ``gaps(conf, seed, server, seqs, control=...)``: the plain reference;
- ``small(conf, full_width=...)``: the configuration cut to a CPU's size,
  for the rehearsal (``bench/tests``).

The reference is the forward pass as the model cards describe it, in
float32 at ``Precision.HIGHEST``, over whole sequences, with no cache and
no batching tricks:

    x = E[tokens]
    per layer:  h = rmsnorm(x) * g1
                q, k, v = h Wq, h Wk, h Wv    (k, v shared by H / KV heads)
                rotate q, k by position (RoPE, halves rotated)
                a = softmax(q k^T / sqrt(hd) + causal, windowed mask) v
                x = x + a Wo
                h = rmsnorm(x) * g2
                x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * g) U

It imports nothing of the system under test and makes its own weights
from the seed, one layer at a time, so that it fits beside nothing else
on the chip.

``control=True`` runs a second stream beside it: the same model with every
linear layer computed in float8 (e4m3, per-tensor weight and per-row
activation scales), the precision step below the served bfloat16. The
benchmark's runs never take it; ``bench/calibrate.py`` and the tests do,
to show that the comparison fails it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench import work


def _shapes(c: dict) -> dict:
    d, H, KV, hd, ff, V = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                           c["head_dim"], c["d_ff"], c["vocab"])
    return {
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, H, hd), d), "wk": ((d, KV, hd), d),
        "wv": ((d, KV, hd), d), "wo": ((H, hd, d), H * hd),
        "gate": ((d, ff), d), "up": ((d, ff), d), "down": ((ff, d), ff),
        "embed": ((V, d), "embed"), "final_norm": ((d,), None),
        "unembed": ((d, V), d),
    }


TREE = W.Tree(
    layer_leaves=("ln1", "ln2", "wq", "wk", "wv", "wo", "gate", "up",
                  "down"),
    top_leaves=("embed", "final_norm", "unembed"),
    paths={
        "ln1": ("layers", "ln1"), "ln2": ("layers", "ln2"),
        "wq": ("layers", "attn", "wq"), "wk": ("layers", "attn", "wk"),
        "wv": ("layers", "attn", "wv"), "wo": ("layers", "attn", "wo"),
        "gate": ("layers", "mlp", "gate"), "up": ("layers", "mlp", "up"),
        "down": ("layers", "mlp", "down"),
        "embed": ("embed", "tok"), "final_norm": ("final_norm",),
        "unembed": ("unembed",),
    },
    shapes=_shapes,
)


served = TREE.served


def small(c: dict, *, full_width: bool = False) -> dict:
    """Two layers and, unless ``full_width``, tiny widths."""
    c = dict(c, n_layers=2)
    if not full_width:
        c.update(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                 vocab=512)
    return c


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
    """One call decodes a token for every row of its ``max_batch x
    max_len`` cache, in use or not: it reads every weight except the
    embedding table, of which it gathers one row per batch row, and the
    whole key and value cache with its position table; it does ``2 x
    max_batch`` operations per matrix weight, and attention's ``q.k`` and
    ``p.v`` over every cache position (the sliding window, where shorter)
    for every head of every layer."""
    p = params(c)
    B, T, L = c["max_batch"], c["max_len"], c["n_layers"]
    H, KV, hd, d = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_model"]
    win = min(c.get("swa_window") or T, T)
    wb = work.BYTES[c["param_dtype"]]
    cb = work.BYTES[c["compute_dtype"]]  # the cache is kept in compute dtype
    matmul_weights = p["total"] - p["embed"]
    weight_bytes = matmul_weights * wb + B * d * wb
    cache_bytes = L * (2 * B * win * KV * hd * cb + B * win * 4)
    attn_flops = L * B * 2 * (2 * H * hd * win)
    return {"flops": 2.0 * B * matmul_weights + attn_flops,
            "bytes": float(weight_bytes + cache_bytes),
            "weight_bytes": float(weight_bytes),
            "cache_bytes": float(cache_bytes)}


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


def _rope(x, theta):
    # x [N, T, H, hd]; position = index along T
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(c: dict, x, w: dict, fp8: bool):
    H, KV, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    T = x.shape[1]
    h = _rms(x, w["ln1"], c["norm_eps"])
    q = _rope(_lin("ntd,dhk->nthk", h, w["wq"], fp8), c["rope_theta"])
    k = _rope(_lin("ntd,dhk->nthk", h, w["wk"], fp8), c["rope_theta"])
    v = _lin("ntd,dhk->nthk", h, w["wv"], fp8)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nthk,nshk->nhts", q, k, precision=HI) / np.sqrt(hd)
    i = jnp.arange(T)
    mask = i[:, None] >= i[None, :]
    if c.get("swa_window"):
        mask &= i[:, None] - i[None, :] < c["swa_window"]
    s = jnp.where(mask, s, -jnp.inf)
    a = jnp.einsum("nhts,nshk->nthk", jax.nn.softmax(s, -1), v,
                   precision=HI)
    x = x + _lin("nthk,hkd->ntd", a, w["wo"], fp8)
    h = _rms(x, w["ln2"], c["norm_eps"])
    g = _lin("ntd,df->ntf", h, w["gate"], fp8)
    u = _lin("ntd,df->ntf", h, w["up"], fp8)
    return x + _lin("ntf,fd->ntd", jax.nn.silu(g) * u, w["down"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(cfg_items, base, l, xs, dtype):
    c = dict(cfg_items)
    w = {n: a.astype(jnp.float32)
         for n, a in TREE.layer(c, base, l, dtype).items()}
    return tuple(_block(c, x, w, fp8) for x, fp8 in zip(xs, (False, True)))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_items, base, tokens, dtype):
    c = dict(cfg_items)
    return TREE.top(c, base, dtype)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _head(cfg_items, base, x_ref, x_ctl, nxt, dtype, control):
    """Per position of one sequence: the reference's best logit minus its
    logit of the next token, and, with the control, minus its logit of
    the token the control puts first."""
    c = dict(cfg_items)
    top = TREE.top(c, base, dtype)
    g = top["final_norm"].astype(jnp.float32)
    u = top["unembed"].astype(jnp.float32)
    lg = jnp.einsum("td,dv->tv", _rms(x_ref, g, c["norm_eps"]), u,
                    precision=HI)
    best = lg.max(-1)
    served = best - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
    if not control:
        return served, served
    lc = _lin("td,dv->tv", _rms(x_ctl, g, c["norm_eps"]), u, True)
    pick = jnp.argmax(lc, -1)
    return served, best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]


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
    toks = jnp.asarray(seqs, jnp.int32)
    x = _embed(items, base, toks, dtype)
    xs = (x, x) if control else (x,)
    for l in range(c["n_layers"]):
        xs = _layer(items, base, l, xs, dtype)
    nxt = jnp.asarray(np.concatenate(
        [seqs[:, 1:], np.zeros_like(seqs[:, :1])], 1), jnp.int32)
    served, ctl = [], []
    for n in range(seqs.shape[0]):
        s, k = _head(items, base, xs[0][n], xs[-1][n], nxt[n], dtype,
                     control)
        served.append(np.asarray(s)[:-1])
        ctl.append(np.asarray(k)[:-1])
    return np.stack(served), (np.stack(ctl) if control else None)
