"""Seeded random weights of a dense GQA transformer, made by the benchmark.

The benchmark makes the served weights itself, on the device in one jitted
call, in the dtype they are served in, and hands them to the server. The
plain reference makes the same numbers again, one layer at a time, from
the same seed: every leaf of layer ``l`` comes from its own key,
``fold_in(fold_in(base, leaf), l)``, so a layer can be made alone and
equals its slice of the stacked array.

Scales follow the usual 1/sqrt(fan-in); norm gains are 1 + 0.05 N(0, 1)
so that a path that drops a gain shows in the comparison.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# leaf id -> (path in the served tree, per-layer?)
_LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "gate", "up", "down")
_TOP_LEAVES = ("embed", "final_norm", "unembed")
_PATHS = {
    "ln1": ("layers", "ln1"), "ln2": ("layers", "ln2"),
    "wq": ("layers", "attn", "wq"), "wk": ("layers", "attn", "wk"),
    "wv": ("layers", "attn", "wv"), "wo": ("layers", "attn", "wo"),
    "gate": ("layers", "mlp", "gate"), "up": ("layers", "mlp", "up"),
    "down": ("layers", "mlp", "down"),
    "embed": ("embed", "tok"), "final_norm": ("final_norm",),
    "unembed": ("unembed",),
}


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


def base_key(seed: int, server: int) -> jax.Array:
    """Seeds go past 32 bits: fold the high part in."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, 1000 + server)


def _leaf(key, name: str, shape, fan, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if fan is None:
        x = 1.0 + 0.05 * x
    elif fan == "embed":
        x = 0.02 * x
    else:
        x = x * (1.0 / math.sqrt(fan))
    return x.astype(dtype)


def _leaf_key(base, name: str):
    ids = _LAYER_LEAVES + _TOP_LEAVES
    return jax.random.fold_in(base, ids.index(name))


def layer(c: dict, base, l, dtype) -> dict:
    """Layer ``l``'s leaves, by leaf name (traceable in ``l``)."""
    sh = _shapes(c)
    return {n: _leaf(jax.random.fold_in(_leaf_key(base, n), l), n, *sh[n],
                     dtype) for n in _LAYER_LEAVES}


def top(c: dict, base, dtype) -> dict:
    sh = _shapes(c)
    return {n: _leaf(_leaf_key(base, n), n, *sh[n], dtype)
            for n in _TOP_LEAVES}


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, x in flat.items():
        *head, last = _PATHS[name]
        d = out
        for h in head:
            d = d.setdefault(h, {})
        d[last] = x
    return out


def served(c: dict, seed: int, server: int, dtype) -> dict:
    """All weights of one server in the served tree layout, stacked over
    layers, made in one jitted call."""

    @jax.jit
    def make(base):
        stacked = jax.vmap(lambda l: layer(c, base, l, dtype))(
            jnp.arange(c["n_layers"]))
        return _nest({**stacked, **top(c, base, dtype)})

    return make(base_key(seed, server))
