"""Seeded random weights, made by the benchmark: the seed discipline that
every family's module (``bench/reference/<reference>.py``) shares.

The benchmark makes the served weights itself, on the device in one jitted
call, in the dtype they are served in, and hands them to the server. The
plain reference makes the same numbers again, one layer at a time, from
the same seed: every leaf of layer ``l`` comes from its own key,
``fold_in(fold_in(base, leaf), l)``, so a layer can be made alone and
equals its slice of the stacked array.

A family describes its served tree as a :class:`Tree`; a leaf's id, the
number folded into the base key, is its place in ``layer_leaves +
top_leaves``.

Scales follow the usual 1/sqrt(fan-in); norm gains are 1 + 0.05 N(0, 1)
so that a path that drops a gain shows in the comparison.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp


def base_key(seed: int, server: int) -> jax.Array:
    """Seeds go past 32 bits: fold the high part in."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, 1000 + server)


def leaf(key, shape, fan, dtype):
    """One leaf: a norm gain where ``fan`` is None, an embedding table
    where it is ``"embed"``, else a matrix scaled by 1/sqrt(fan)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if fan is None:
        x = 1.0 + 0.05 * x
    elif fan == "embed":
        x = 0.02 * x
    else:
        x = x * (1.0 / math.sqrt(fan))
    return x.astype(dtype)


@dataclasses.dataclass(frozen=True, eq=False)
class Tree:
    """A served tree whose layers are stacked on a leading axis.

    ``layer_leaves`` and ``top_leaves`` name its leaves, per layer and
    once; ``paths`` gives each leaf's path in the served tree; ``shapes``
    maps a configuration to each leaf's ``(shape, fan)`` (shape of one
    layer for a per-layer leaf; ``fan`` as :func:`leaf` takes it)."""

    layer_leaves: tuple[str, ...]
    top_leaves: tuple[str, ...]
    paths: dict[str, tuple[str, ...]]
    shapes: Callable[[dict], dict]

    def _key(self, base, name: str):
        ids = self.layer_leaves + self.top_leaves
        return jax.random.fold_in(base, ids.index(name))

    def layer(self, c: dict, base, l, dtype) -> dict:
        """Layer ``l``'s leaves, by leaf name (traceable in ``l``)."""
        sh = self.shapes(c)
        return {n: leaf(jax.random.fold_in(self._key(base, n), l), *sh[n],
                        dtype) for n in self.layer_leaves}

    def top(self, c: dict, base, dtype) -> dict:
        sh = self.shapes(c)
        return {n: leaf(self._key(base, n), *sh[n], dtype)
                for n in self.top_leaves}

    def _nest(self, flat: dict) -> dict:
        out: dict = {}
        for name, x in flat.items():
            *head, last = self.paths[name]
            d = out
            for h in head:
                d = d.setdefault(h, {})
            d[last] = x
        return out

    def served(self, c: dict, seed: int, server: int, dtype) -> dict:
        """All weights of one server in the served tree layout, stacked
        over layers, made in one jitted call."""

        @jax.jit
        def make(base):
            stacked = jax.vmap(lambda l: self.layer(c, base, l, dtype))(
                jnp.arange(c["n_layers"]))
            return self._nest({**stacked, **self.top(c, base, dtype)})

        return make(base_key(seed, server))
