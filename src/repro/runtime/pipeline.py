"""Optional GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Not used by the assigned cells (DESIGN.md §7: DP x FSDP x TP suffices at
256-512 chips), but 1000+-node deployments of the largest configs want a
``pipe`` axis; this module provides the schedule and is tested on fake
devices.

Implementation: ``shard_map`` over the pipe axis — each rank holds one
stage's parameters; activations rotate rank->rank+1 with
``lax.ppermute``. The loop runs ``n_micro + n_stages - 1`` ticks (the
GPipe fill/drain bubble); rank r computes on ticks r..r+n_micro-1.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(
    mesh: Mesh,
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params: jax.Array,   # [n_stages, ...] (stacked per-stage)
    microbatches: jax.Array,   # [n_micro, mb, ...]
    *,
    axis: str = "pipe",
) -> jax.Array:
    """Runs ``y = stage_{n-1}(...stage_0(x))`` for every microbatch with
    the GPipe rotation schedule. Returns [n_micro, mb, ...] outputs."""
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_rank(params, mb):  # params [1,...]; mb [n_micro, b, ...]
        p = jax.tree_util.tree_map(lambda a: a[0], params)
        rank = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(mb[0])          # activation in flight
        outs = jnp.zeros_like(mb)            # only the last rank's are real

        def tick(carry, t):
            buf, outs = carry
            # rank 0 injects microbatch t (when in range)
            inject = jnp.where(t < n_micro, t, 0)
            buf = jnp.where(rank == 0, mb[inject], buf)
            active = jnp.logical_and(t - rank >= 0, t - rank < n_micro)
            y = stage_fn(p, buf)
            y = jnp.where(active, y, buf)
            # the last rank records its completed microbatch
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = jnp.logical_and(rank == n_stages - 1, active)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, y, outs[done_idx]), done_idx, 0
            )
            # rotate activations to the next stage
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        return outs

    sm = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(axis),  # each rank emits its view; stage n-1 is truth
        check_vma=False,
    )
    all_outs = sm(stage_params, microbatches)
    # out has a leading pipe dim folded into axis 0 of outs per rank:
    # [n_stages * n_micro, ...]; the final stage's block is the result
    return all_outs.reshape(n_stages, n_micro, *microbatches.shape[1:])[-1]
