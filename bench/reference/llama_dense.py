"""Plain reference of a dense decoder with grouped-query attention.

The forward pass of ``smollm_360m`` and ``h2o_danube3_4b`` as their model
cards describe it, in float32 at ``Precision.HIGHEST``, over whole
sequences, with no cache and no batching tricks:

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
from the seed (``bench.weights``), one layer at a time, so that it fits
beside nothing else on the chip.

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
         for n, a in W.layer(c, base, l, dtype).items()}
    return tuple(_block(c, x, w, fp8) for x, fp8 in zip(xs, (False, True)))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_items, base, tokens, dtype):
    c = dict(cfg_items)
    return W.top(c, base, dtype)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _head(cfg_items, base, x_ref, x_ctl, nxt, dtype, control):
    """Per position of one sequence: the reference's best logit minus its
    logit of the next token, and, with the control, minus its logit of
    the token the control puts first."""
    c = dict(cfg_items)
    top = W.top(c, base, dtype)
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
