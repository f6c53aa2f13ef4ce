"""The decode step writes each row's new key, value and position in place
at ``[layer, b, pos % W]`` of the stacked cache, and nothing else.

The reference is the scatter formulation the step used before it carried
the cache through its layer scan: slice the layer, ``.at[b, slot].set``
the new entries, attend over the slice. It is written out here, and the
step is run with it swapped in for ``attention_decode``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke, list_archs
from repro.launch.inputs import make_decode_inputs
from repro.models import attention as attn
from repro.models.base import init_tree
from repro.models.layers import apply_rope
from repro.models.registry import build_model
from repro.runtime.sharding import Sharder

B, MAX_LEN = 3, 24
# distinct positions; the last wraps every ring (W is 24, or 16 for the
# smoke configurations' sliding and local windows)
POSITIONS = (2, 9, 29)
# every configuration whose decode cache holds k/v attention layers: dense,
# sliding window, vlm with M-RoPE, moe with a leading dense layer, hybrid.
# The latent cache (deepseek_v2_lite) has its own test in test_deepseek_v2.py
ARCHS = [a for a in list_archs()
         if get_smoke(a).supports_decode and get_smoke(a).family != "ssm"
         and not get_smoke(a).kv_lora_rank]
# the layer scan, and its Python unroll (``scan_layers=False``, the dry-run's
# cost probes) for one configuration of each family
CASES = [(a, True) for a in ARCHS] + [
    (a, False) for a in ("smollm_360m", "qwen2_vl_7b", "deepseek_moe_16b",
                         "recurrentgemma_9b")]


def _scatter_attention_decode(params, cfg, sharder, x, cache, layer,
                              positions, *, window=None):
    dt = x.dtype
    Bx = x.shape[0]
    if positions.ndim == 2:  # [3, B] M-RoPE streams
        pos_t, rope_pos = positions[0], positions[:, :, None]
    else:
        pos_t, rope_pos = positions, positions[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = apply_rope(q, rope_pos, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, rope_pos, cfg.rope_theta, cfg.mrope_sections)

    layer_cache = jax.tree_util.tree_map(lambda a: a[layer], cache)
    W = layer_cache["k"].shape[1]
    slots = (pos_t % W).astype(jnp.int32)
    bidx = jnp.arange(Bx)
    k_cache = layer_cache["k"].at[bidx, slots].set(
        k[:, 0].astype(layer_cache["k"].dtype))
    v_cache = layer_cache["v"].at[bidx, slots].set(
        v[:, 0].astype(layer_cache["v"].dtype))
    pos_cache = layer_cache["pos"].at[bidx, slots].set(pos_t.astype(jnp.int32))

    D = q.shape[-1]
    KV = k_cache.shape[2]
    G = q.shape[2] // KV
    qr = (q.astype(jnp.float32) * (D ** -0.5)).reshape(Bx, KV, G, D)
    s = jnp.einsum("bkgd,bwkd->bkgw", qr, k_cache.astype(jnp.float32))
    valid = (pos_cache >= 0) & (pos_cache <= pos_t[:, None])
    if window is not None:
        valid = valid & (pos_t[:, None] - pos_cache < window)
    s = jnp.where(valid[:, None, None, :], s, attn._NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, v_cache.astype(jnp.float32))
    o = o.reshape(Bx, 1, q.shape[2], D).astype(dt)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
    new = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    return y, jax.tree_util.tree_map(
        lambda a, n: a.at[layer].set(n), cache, new)


def _attn_caches(cfg, cache) -> dict:
    """Every stacked attention cache ({k, v, pos}) of a decode cache."""
    if cfg.family == "hybrid":
        return {"superblocks": cache["superblocks"]["attn"]}
    return {n: cache[n] for n in ("dense_layers", "layers") if n in cache}


def _filled(c: dict, key) -> dict:
    """``c`` filled: random keys and values, and in each slot the last
    position before the row's current one that maps to it (-1: none)."""
    L, _, W = c["pos"].shape
    kk, kv = jax.random.split(key)
    slot = np.arange(W)
    last = np.stack([p - 1 - (p - 1 - slot) % W for p in POSITIONS])
    return {"k": jax.random.normal(kk, c["k"].shape).astype(c["k"].dtype),
            "v": jax.random.normal(kv, c["v"].shape).astype(c["v"].dtype),
            "pos": jnp.broadcast_to(jnp.asarray(np.maximum(last, -1),
                                                jnp.int32), (L, B, W))}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}")) if a.dtype.kind == "f" \
        else a


@pytest.mark.parametrize(
    "arch_id,scan_layers", CASES,
    ids=[f"{a}-{'scan' if s else 'unrolled'}" for a, s in CASES])
def test_decode_writes_one_slot_per_row_and_layer(arch_id, scan_layers,
                                                  monkeypatch):
    cfg = dataclasses.replace(get_smoke(arch_id), scan_layers=scan_layers)
    model = build_model(cfg)
    sharder = Sharder(None)
    params = init_tree(jax.random.PRNGKey(0), model.param_specs(),
                       cfg.param_dtype)
    cache, tok, _ = make_decode_inputs(cfg, B, max_len=MAX_LEN,
                                       key=jax.random.PRNGKey(1))
    for i, c in enumerate(_attn_caches(cfg, cache).values()):
        c.update(_filled(c, jax.random.PRNGKey(2 + i)))
    pos = jnp.asarray(POSITIONS, jnp.int32)
    if cfg.mrope_sections is not None:  # distinct height and width streams
        pos = jnp.stack([pos, pos + 1, pos + 2])

    def run():
        return jax.jit(lambda p, c, t, q: model.decode_step(
            p, c, t, q, sharder))(params, cache, tok, pos)

    logits, new, _ = run()
    monkeypatch.setattr(attn, "attention_decode", _scatter_attention_decode)
    ref_logits, ref, _ = run()

    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    olds, news, refs = (_attn_caches(cfg, x) for x in (cache, new, ref))
    assert olds.keys() == news.keys() and news
    for name in olds:
        L, _, W = olds[name]["pos"].shape
        written = np.zeros((L, B, W), bool)
        for b, p in enumerate(POSITIONS):
            written[:, b, p % W] = True
        for leaf in ("k", "v", "pos"):
            o, n, r = (np.asarray(x[name][leaf])
                       for x in (olds, news, refs))
            # every element outside the written slots is the input's, bit
            # for bit
            np.testing.assert_array_equal(_bits(n)[~written],
                                          _bits(o)[~written])
            if leaf == "pos":
                np.testing.assert_array_equal(
                    n[written].reshape(L, B), np.broadcast_to(POSITIONS, (L, B)))
                assert (o[written] != n[written]).all()
            else:
                np.testing.assert_allclose(n[written], r[written],
                                           rtol=1e-5, atol=1e-5)
