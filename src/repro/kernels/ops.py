"""jit'd public wrappers for the Pallas kernels.

``interpret`` is explicit and defaults to False: the kernels compile for
the TPU, and a caller that wants the Pallas interpreter (CPU tests,
``JAX_PLATFORMS=cpu``) passes ``interpret=True``. Nothing falls back to
interpret mode on its own. Model code calls these through
``cfg.attn_backend="pallas"`` etc.; layouts are adapted here
([B,S,H,D] model convention -> [B,H,S,D] kernel convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    interpret: bool = False):
    """Model layout: q [B,S,H,D]; k,v [B,T,KV,D] -> [B,S,H,D]."""
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    o = _fa.flash_attention_fwd(qT, kT, vT, causal=causal, window=window,
                                interpret=interpret)
    return jnp.swapaxes(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode(q, k_cache, v_cache, cache_pos, q_pos, *,
                 window: Optional[int] = None,
                 interpret: bool = False):
    """q [B,H,D]; caches [B,W,KV,D] (model layout) -> [B,H,D]."""
    kT = jnp.swapaxes(k_cache, 1, 2)
    vT = jnp.swapaxes(v_cache, 1, 2)
    return _dec.flash_decode(q, kT, vT, cache_pos, q_pos, window=window,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256,
             interpret: bool = False):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru(a, b, h0, *, interpret: bool = False):
    return _rg.rglru_scan_kernel(a, b, h0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(x, w, *, interpret: bool = False):
    return _gmm.moe_gmm(x, w, interpret=interpret)
