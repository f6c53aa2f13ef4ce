"""Split-KV flash-decode kernel: one new token against a long KV cache.

Grid (batch, kv_head, kv_blocks): each kv block folds its partial softmax
into VMEM scratch (running m/l/acc per q-head-group) — flash-decoding
adapted to the TPU's sequential minor grid axis instead of GPU thread-block
reductions. Validity is positional (cache slots carry absolute positions:
ring buffers for SWA/local attention come for free).

TPU block layout: a block's last two dims must be multiples of (8, 128)
or span the array, so the slot positions ride as ``[B, 1, W]`` (block
``(1, 1, blk_k)``) and the per-row query positions are a scalar-prefetch
operand in SMEM rather than a ``(1,)`` VMEM block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float,
            window: Optional[int], blk_k: int, G: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                # [blk_k, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, blk_k]
    cpos = cpos_ref[0]                                  # [1, blk_k] int32
    qpos = qpos_ref[pl.program_id(0)]                   # [] int32 (SMEM)
    valid = jnp.logical_and(cpos >= 0, cpos <= qpos)
    if window is not None:
        valid = jnp.logical_and(valid, qpos - cpos < window)
    s = jnp.where(valid, s, NEG)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,          # [B, H, D] one token per row
    k_cache: jax.Array,    # [B, KV, W, D]
    v_cache: jax.Array,    # [B, KV, W, D]
    cache_pos: jax.Array,  # [B, W] absolute positions (-1 empty)
    q_pos: jax.Array,      # [B] absolute position of the new token
    *,
    window: Optional[int] = None,
    blk_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    KV, W = k_cache.shape[1], k_cache.shape[2]
    assert H % KV == 0
    G = H // KV
    blk_k = min(blk_k, W)
    pk = (-W) % blk_k
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pk), (0, 0)))
        cache_pos = jnp.pad(cache_pos, ((0, 0), (0, pk)),
                            constant_values=-1)
    Wp = k_cache.shape[2]
    nk = Wp // blk_k
    qg = q.reshape(B, KV, G, D)

    # index maps also receive the scalar-prefetch ref (unused here)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=D ** -0.5, window=window,
                          blk_k=blk_k, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, nk),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, j, qp: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, blk_k, D),
                             lambda b, h, j, qp: (b, h, j, 0)),
                pl.BlockSpec((1, 1, blk_k, D),
                             lambda b, h, j, qp: (b, h, j, 0)),
                pl.BlockSpec((1, 1, blk_k), lambda b, h, j, qp: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, qp: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), qg, k_cache, v_cache, cache_pos[:, None, :])
    return out.reshape(B, H, D)
