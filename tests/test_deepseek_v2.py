"""DeepSeek-V2-Lite on the serve path: latent attention with a latent
cache and absorbed decode, YaRN rope, and an expert share served dropless,
against the plain reference (``deepseek_v2_reference.py``) at ``smoke()``
size on seeded random weights. Logits are compared, never sampled tokens.

Tolerances: the program and the reference both compute in float32 here
(``smoke()``'s compute dtype), the reference at ``Precision.HIGHEST``; what
is left is float32 rounding in a different order, under 1e-5 of logits of
size 1-5. Each limit below is 1e-4: ten times that, and some hundred times
below what bfloat16 compute gives (its rounding, 2^-8 relative, reaches
1e-2 on these logits), so a step computed in bfloat16 fails every one.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_reference as ref
from repro.configs.base import get_arch, get_smoke
from repro.core.policies import SchedCoop
from repro.core.threads import UsfRuntime
from repro.core.topology import Topology
from repro.launch.inputs import make_decode_inputs
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.base import init_tree
from repro.models.layers import mlp, yarn_freqs, yarn_mscale
from repro.models.registry import build_model
from repro.runtime.sharding import Sharder
from repro.serve.engine import InferenceServer, Request
from repro.train.step import make_serve_step

ATOL = 1e-4  # see the module docstring
T = 12       # positions decoded teacher-forced


def _setup(**over):
    cfg = dataclasses.replace(get_smoke("deepseek_v2_lite"), **over)
    model = build_model(cfg)
    params = init_tree(jax.random.PRNGKey(0), model.param_specs(),
                       cfg.param_dtype)
    return cfg, model, params


def _tokens(cfg, n, t, seed=4):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, t),
                                         0, cfg.vocab, jnp.int32))


def _decode(cfg, model, params, toks):
    """Teacher-forced decode of every row of ``toks`` [B, T] through the
    served step and its latent cache: logits [B, T, V]."""
    B, n = toks.shape
    cache, _, _ = make_decode_inputs(cfg, B, max_len=n,
                                     key=jax.random.PRNGKey(5))
    step = jax.jit(make_serve_step(model, Sharder(None)))
    out = []
    for i in range(n):
        lg, cache, _ = step(params, cache, jnp.asarray(toks[:, i]),
                            jnp.full((B,), i, jnp.int32))
        out.append(np.asarray(lg, np.float32))
    return np.stack(out, 1), cache


def test_teacher_forced_decode_matches_the_reference():
    """Every position of a decode through the latent cache, absorbed
    attention and the dropless expert share against the plain reference's
    full forward, for two rows."""
    cfg, model, params = _setup()
    toks = _tokens(cfg, 2, T)
    dec, _ = _decode(cfg, model, params, toks)
    want = np.asarray(ref.forward(dataclasses.asdict(cfg), params, toks))
    np.testing.assert_allclose(dec, want, rtol=0, atol=ATOL)


def test_absorbed_decode_matches_the_full_sequence_forward():
    """The decode's absorbed attention against the program's own
    non-absorbed full-sequence forward (capacity ample, so the forward's
    capacity rule drops nothing)."""
    cfg, model, params = _setup(capacity_factor=16.0)
    toks = _tokens(cfg, 2, T, seed=6)
    dec, _ = _decode(cfg, model, params, toks)
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), toks.shape)
    full, _ = jax.jit(lambda p, b: model.forward(p, b, Sharder(None)))(
        params, {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)})
    np.testing.assert_allclose(dec, np.asarray(full), rtol=0, atol=ATOL)


def test_yarn_frequencies_and_softmax_factor_as_published():
    """DeepSeek-V2-Lite's rope: dim 64, base 1e4, factor 40 over 4096,
    beta 32/1. The published correction range is floor(10.47) = 10 to
    ceil(22.51) = 23: pairs up to 10 keep 1e4^(-2i/64), pairs from 23 on
    take it over 40, and pair 16 blends them 7/13 : 6/13. The score factor
    is (0.1 * 0.707 * ln 40 + 1)^2 = 1.5896, the rope amplitude 1."""
    cfg = get_arch("deepseek_v2_lite")
    f = np.asarray(yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0), np.float64)
    base = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(f[16], base[16] * (7 / 13 + 6 / 13 / 40),
                               rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    m = yarn_mscale(40.0, 0.707)
    assert m ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale) / \
        yarn_mscale(cfg.rope_scaling_factor, cfg.yarn_mscale_all_dim) == 1.0
    from repro.models.attention import _mla_rope
    _, amp, scale = _mla_rope(cfg)
    assert amp == 1.0
    assert scale == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)


def _moe_params(d, E, ff, n_shared, key):
    ks = jax.random.split(key, 7)
    s = 1 / math.sqrt(d)
    return {
        "router": jax.random.normal(ks[0], (d, E)) * s,
        "wg": jax.random.normal(ks[1], (E, d, ff)) * s,
        "wu": jax.random.normal(ks[2], (E, d, ff)) * s,
        "wd": jax.random.normal(ks[3], (E, ff, d)) / math.sqrt(ff),
        "shared": {"gate": jax.random.normal(ks[4], (d, n_shared * ff)) * s,
                   "up": jax.random.normal(ks[5], (d, n_shared * ff)) * s,
                   "down": jax.random.normal(ks[6], (n_shared * ff, d))
                   / math.sqrt(n_shared * ff)},
    }


def _moe_cfg(**over):
    """DeepSeek-V2-Lite's expert layer at tiny widths: 64 routed, top 6,
    2 shared, no renormalisation; a share of 8."""
    return dataclasses.replace(get_smoke("deepseek_v2_lite"), d_model=32,
                               expert_d_ff=16, n_experts=64, top_k=6,
                               n_shared_experts=2, expert_parallel=8, **over)


def test_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer split over 8 chips of 8 experts each: each share's
    routed part on the serve path, summed, plus the shared experts once,
    is the uncut layer of the reference. Share ``s`` holds experts 8s ..
    8s+7: its router's columns rolled so that they come first."""
    cfg = _moe_cfg()
    w = _moe_params(32, 64, 16, 2, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32))
    routed_only = dataclasses.replace(cfg, n_shared_experts=0)
    total = mlp(w["shared"], x, "silu")
    for s in range(8):
        part = {k: w[k][8 * s:8 * s + 8] for k in ("wg", "wu", "wd")}
        router = jnp.roll(w["router"], -8 * s, axis=1)
        y, _ = moe_mod.moe_share_decode({**part, "router": router},
                                        routed_only, x)
        total = total + y
    want = ref.experts(dataclasses.asdict(cfg), x, w)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=ATOL)


def test_biased_router_is_served_dropless():
    """Every token's first pick is held expert 0. The serve path gives the
    reference's result; today's capacity rule, which routes the batch as
    one group with capacity int(B K 1.25 / E) + 1, drops most of those
    tokens."""
    cfg = _moe_cfg()
    w = _moe_params(32, 64, 16, 2, jax.random.PRNGKey(3))
    w = {**w, "wg": w["wg"][:8], "wu": w["wu"][:8], "wd": w["wd"][:8]}
    bias = jax.random.normal(jax.random.PRNGKey(4), (32,))
    x = bias + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (1, 32, 32))
    w["router"] = w["router"].at[:, 0].set(bias * 10.0)  # all pick 0
    y, counts = moe_mod.moe_share_decode(w, cfg, x)
    want = np.asarray(ref.experts(dataclasses.asdict(cfg), x, w))
    np.testing.assert_allclose(np.asarray(y), want, rtol=0, atol=ATOL)
    assert int(counts["expert_peak_picks"]) == 32  # all 32 on expert 0
    capped, _ = moe_mod.moe_block(w, cfg, Sharder(None), x)
    assert moe_mod._capacity(32, 64, 6, cfg.capacity_factor) < 32
    assert np.abs(np.asarray(capped) - want).max() > 100 * ATOL


@pytest.mark.parametrize("masked", [False, True])
def test_counts_are_the_picks_of_held_experts(masked):
    """``expert_picks`` and ``expert_peak_picks`` against a recount from
    the router's own top-k, over every token or over the rows marked in
    use; the mask changes no output."""
    cfg = _moe_cfg()
    w = _moe_params(32, 64, 16, 2, jax.random.PRNGKey(7))
    w = {**w, "wg": w["wg"][:8], "wu": w["wu"][:8], "wd": w["wd"][:8]}
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, 32))
    rows = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(9), 0.3, (40,)))
    y_all, _ = moe_mod.moe_share_decode(w, cfg, x)
    y, counts = moe_mod.moe_share_decode(
        w, cfg, x, rows=jnp.asarray(rows) if masked else None)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(x[0] @ w["router"], -1)
    idx = np.asarray(jax.lax.top_k(p, 6)[1])
    if masked:
        assert 0 < rows.sum() < 40
        idx = idx[rows]
    per = np.array([(idx == e).sum() for e in range(8)])
    assert int(counts["expert_picks"]) == per.sum()
    assert int(counts["expert_peak_picks"]) == per.max()


def _random_latent_cache(cfg, positions, W, seed):
    """Both latent stacks of ``W`` slots filled with random latents and
    rope keys, each row's slots holding the ``W`` positions before its
    own (-1 where none): (cache, tokens)."""
    cache, tok, _ = make_decode_inputs(cfg, len(positions), max_len=W,
                                       key=jax.random.PRNGKey(1))
    last = np.stack([p - 1 - (p - 1 - np.arange(W)) % W for p in positions])
    for i, n in enumerate(("dense_layers", "layers")):
        c = cache[n]
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed + i))
        c["c_kv"] = jax.random.normal(k1, c["c_kv"].shape)
        c["k_pe"] = jax.random.normal(k2, c["k_pe"].shape)
        c["pos"] = jnp.broadcast_to(jnp.asarray(np.maximum(last, -1)),
                                    c["pos"].shape)
    return cache, tok


def test_decode_writes_one_latent_slot_per_row_and_layer():
    """One step writes each row's latent, rope key and position at
    ``[layer, b, pos % W]`` of every stacked latent cache, and nothing
    else, bit for bit."""
    cfg, model, params = _setup()
    B, W, positions = 3, 16, (2, 9, 21)
    cache, tok = _random_latent_cache(cfg, positions, W, seed=10)
    old = jax.tree_util.tree_map(np.asarray, cache)
    _, new, counts = jax.jit(lambda p, c, t, q: model.decode_step(
        p, c, t, q, Sharder(None)))(params, cache, tok,
                                    jnp.asarray(positions, jnp.int32))
    assert set(new) == {"dense_layers", "layers"}
    assert set(counts) == set(moe_mod.COUNTS) == set(model.step_counts)
    for n in ("dense_layers", "layers"):
        L = old[n]["pos"].shape[0]
        hit = np.zeros((L, B, W), bool)
        for b, p in enumerate(positions):
            hit[:, b, p % W] = True
        for leaf in ("c_kv", "k_pe", "pos"):
            o, w_ = old[n][leaf], np.asarray(new[n][leaf])
            np.testing.assert_array_equal(w_[~hit], o[~hit])
            assert (w_[hit] != o[hit]).all()
        np.testing.assert_array_equal(
            np.asarray(new[n]["pos"])[hit].reshape(L, B),
            np.broadcast_to(positions, (L, B)))


def _put_by_select(buf, new, layer, slot):
    """The latent cache's former write: a select over the whole layer put
    back with a dynamic update (``attention._put``)."""
    hit = jnp.arange(buf.shape[2]) == slot[:, None]
    return attn_mod._put(buf, new[:, None], layer, hit)[1]


@pytest.mark.parametrize("positions", [
    (2, 9, 13, 5),     # distinct positions
    (7, 7, 7, 3),      # three rows at one position
    (16, 21, 31, 40),  # at and past W: the ring
], ids=["distinct", "same", "ring"])
def test_slot_scatter_writes_what_the_layer_select_wrote(positions,
                                                          monkeypatch):
    """One step from a random latent cache writes ``c_kv``, ``k_pe`` and
    ``pos`` of both stacks bit for bit as the whole-layer select did, and
    its logits match within float32 rounding."""
    cfg, model, params = _setup()
    cache, tok = _random_latent_cache(cfg, positions, 16, seed=20)
    pos = jnp.asarray(positions, jnp.int32)

    def step(c):
        return jax.jit(lambda p, c, t, q: model.decode_step(
            p, c, t, q, Sharder(None)))(params, c, tok, pos)

    got, new, _ = step(cache)
    monkeypatch.setattr(attn_mod, "_put_slots", _put_by_select)
    want, old, _ = step(cache)
    for n in ("dense_layers", "layers"):
        for leaf in ("c_kv", "k_pe", "pos"):
            np.testing.assert_array_equal(np.asarray(new[n][leaf]),
                                          np.asarray(old[n][leaf]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)


#: the smoke decode of the existing expert layers, 4 steps of 4 rows from
#: a fresh cache, before the expert share existed: per step the sum and
#: the absolute sum of the logits and row 0's first three logits
GOLDEN = {
    "deepseek_moe_16b": [
        (-7.28462673118338, 789.6494703083299, 0.05600229650735855,
         -0.5754790306091309, 1.193841576576233),
        (0.9103665819275193, 798.5147485697526, 0.32048335671424866,
         -1.9347158670425415, 1.6015557050704956),
        (-36.65920390340034, 784.7984546314692, 0.47268369793891907,
         -1.4255048036575317, 1.542419672012329),
        (22.361373105377425, 783.1050863570417, 0.4499508738517761,
         -0.8797177076339722, 1.6588168144226074)],
    "grok_1_314b": [
        (-19.076507296937052, 821.9581612912589, 0.717232882976532,
         0.9878594875335693, 0.4488008916378021),
        (-50.58559554745443, 811.1438873864245, 0.12458579987287521,
         0.557528018951416, -1.9814940690994263),
        (-61.92034239252098, 840.7029956693295, -1.1388307809829712,
         0.8947843909263611, -0.9791274070739746),
        (-41.367995428387076, 847.3962507722899, -0.34521612524986267,
         0.23632121086120605, -1.524605393409729)],
}


@pytest.mark.parametrize("arch_id", sorted(GOLDEN))
def test_existing_expert_layers_decode_as_before(arch_id):
    """``deepseek_moe_16b`` and ``grok_1_314b`` hold every expert and keep
    the capacity layer with renormalised gates: their decode is what it
    was."""
    cfg = get_smoke(arch_id)
    assert cfg.experts_held == cfg.n_experts and cfg.norm_topk_prob
    model = build_model(cfg)
    assert not model.step_counts
    params = init_tree(jax.random.PRNGKey(0), model.param_specs(),
                       cfg.param_dtype)
    cache, tok, pos = make_decode_inputs(cfg, 4, max_len=16,
                                         key=jax.random.PRNGKey(1))
    step = jax.jit(make_serve_step(model, Sharder(None)))
    for i, want in enumerate(GOLDEN[arch_id]):
        lg, cache, counts = step(params, cache, tok, pos + i)
        assert counts == {}
        got = np.asarray(lg, np.float64)
        np.testing.assert_allclose(
            [got.sum(), np.abs(got).sum(), *got[0, :3]], want,
            rtol=1e-5, atol=1e-4)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)


def test_expert_counts_reach_the_server_stats():
    """The engine adds each step's counts of the rows in use to
    ``stats()``; a model with no expert share reports none. One request
    on 16 rows: the 15 idle rows, routed too, would add some 15 times the
    picks of the row in use."""
    usf = UsfRuntime(Topology(2, 1), SchedCoop())
    try:
        servers = [InferenceServer(a, get_smoke(a), usf, max_batch=16,
                                   max_len=32)
                   for a in ("deepseek_v2_lite", "deepseek_moe_16b")]
        for s in servers:
            s.start()
        reqs = [s.submit(Request(tokens=[1, 2, 3], max_new=4))
                for s in servers]
        for r in reqs:
            assert r.done.wait(timeout=120.0)
        for s in servers:
            s.stop()
            assert usf.join(s._task, timeout=60.0)
    finally:
        usf.shutdown(timeout=5.0)
    ds, other = (s.stats() for s in servers)
    cfg = get_smoke("deepseek_v2_lite")
    # the one row in use picks top_k experts a layer, of which the held
    # experts take some
    assert ds["rows"] == ds["steps"]
    picks = ds["rows"] * cfg.top_k * (cfg.n_layers - cfg.first_k_dense)
    assert 0 < ds["expert_picks"] <= picks
    assert ds["expert_picks"] / cfg.experts_held <= \
        ds["expert_peak_picks"] <= ds["expert_picks"]
    assert not {"expert_picks", "expert_peak_picks"} & other.keys()
