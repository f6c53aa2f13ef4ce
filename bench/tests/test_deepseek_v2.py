"""The DeepSeek-V2 family module (``bench/reference/deepseek_v2.py``): its
served tree is the server's, its step count is the hand count, its
reference is the repository's plain reference (``tests/
deepseek_v2_reference.py``), and the comparison that decides ``correct``
passes the program and fails the float8 control. Its cell's rehearsal, the
planted faults among them, is ``test_rehearsal.py``'s, parametrised over
every cell."""

import dataclasses
import importlib.util
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import traffic as T
from bench.harness import BENCH, ROOT
from bench.tests.test_rehearsal import BENCHMARK, shrink

FAMILY = harness.load_module("reference", "deepseek_v2")
CELL = "deepseek_v2_lite.solo.batch192"


def conf():
    return json.loads((BENCH / "configs" / "deepseek_v2_lite.json")
                      .read_text())


def repo_reference():
    path = ROOT / "tests" / "deepseek_v2_reference.py"
    spec = importlib.util.spec_from_file_location("deepseek_v2_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_file_states_the_share_and_keeps_every_width():
    c = conf()
    assert c["reduced"] == ["n_routed_experts"]
    assert c["n_routed_experts"] == c["experts_held"] == 8
    assert c["n_experts"] // c["expert_parallel"] == c["experts_held"]
    assert harness.arch_config(c).experts_held == c["experts_held"]
    assert c["published_n_routed_experts"] == c["n_experts"] == 64
    assert c["num_hidden_layers"] == c["n_layers"] == 27
    assert "8 chips" in c["deployment"]
    assert any("1/8" in n for n in c["notes"])


def test_served_tree_fits_the_servers():
    from repro.models.base import abstract_tree
    from repro.models.registry import build_model

    c = FAMILY.small(conf())
    cfg = harness.arch_config(c)
    want = jax.tree_util.tree_map(
        lambda s: (s.shape, s.dtype),
        abstract_tree(build_model(cfg).param_specs(), c["param_dtype"]))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                 FAMILY.served(c, 3, 0, c["param_dtype"]))
    assert got == want


def test_serve_step_is_the_hand_count():
    """At ``small`` size (2 layers, d 64, 4 heads, latent 32, rope 8, nope
    16, v 16, dense width 128, expert width 32, vocab 512; 8 of 64
    experts held, top 6, 2 shared; 4 rows of 16, bfloat16)."""
    c = dict(FAMILY.small(conf()), max_batch=4, max_len=16)
    d, H, V, ff, e, B, Tn = 64, 4, 512, 128, 32, 4, 16
    attn = (2 * d + d * H * 24 + d * 40 + 32 + 32 * H * 32 + H * 16 * d)
    dense = attn + 3 * d * ff
    experts = 8 * 3 * d * e
    moe = attn + d * 64 + experts + 3 * d * 2 * e
    total = 2 * V * d + d + dense + moe
    assert FAMILY.params(c)["total"] == total
    w = FAMILY.serve_step(c)
    matmul = total - V * d
    assert w["weight_bytes"] == (matmul - d * 64) * 2 + d * 64 * 4 + B * d * 2
    assert w["latent_cache_bytes"] == 2 * B * Tn * 40 * 2
    assert w["cache_bytes"] == w["latent_cache_bytes"] + 2 * B * Tn * 4
    assert w["bytes"] == w["weight_bytes"] + w["cache_bytes"]
    assert w["expert_bytes"] == experts * 2
    assert w["flops"] == (2 * B * (matmul - experts)
                          + 2 * (B * 6 / 64) * experts
                          + 2 * B * H * (2 * Tn * 40 + 2 * Tn * 32))
    assert (w["expert_layers"], w["experts_held"]) == (1, 8)


def test_serve_step_at_the_cells_size():
    """3.111B weights; 10.41 GB and 627 GFLOP a step, of which latent
    attention (its cache and weights) and the experts (held and shared)
    are about 94% of the bytes."""
    w = FAMILY.serve_step(conf())
    assert FAMILY.params(conf())["total"] == 3_110_989_312
    assert w["bytes"] == pytest.approx(10.41e9, rel=1e-3)
    assert w["flops"] == pytest.approx(626.5e9, rel=1e-3)
    shared = 26 * 3 * 2048 * 2816 * 2
    mla = FAMILY.params(conf())["attn"] * 2
    share = (w["latent_cache_bytes"] + mla + w["expert_bytes"]
             + shared) / w["bytes"]
    assert 0.93 < share < 0.95
    assert math.isclose(w["latent_cache_bytes"], 4.586e9, rel_tol=1e-3)


def test_the_benchmark_reference_is_the_repositorys():
    """At a small size, on the benchmark's weights: the gaps the family's
    reference gives are the repository reference's logits' gaps."""
    ref = repo_reference()
    c = dict(FAMILY.small(conf()), param_dtype="float32")
    cfg = harness.arch_config(c)
    seqs = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (3, 20), 0,
                                         c["vocab"], jnp.int32))
    params = FAMILY.served(c, 5, 0, "float32")
    lg = np.asarray(ref.forward(dataclasses.asdict(cfg), params, seqs))
    nxt = np.take_along_axis(lg[:, :-1], seqs[:, 1:, None], -1)[..., 0]
    want = lg[:, :-1].max(-1) - nxt
    got, _ = FAMILY.gaps(c, 5, 0, seqs)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    assert want.max() > 0.1  # not a vacuous comparison


def test_float8_control_fails_the_limit():
    """At full width (the dense layer and one expert layer), the program
    reads under its limit and the float8 control, at the same prompts and
    tokens, over it."""
    c, mix = shrink(conf(), T.load_mix("solo.batch192"), full_width=True)
    seed = 23
    reqs = T.plan(mix, seed=seed, seconds=3.0, vocab=c["vocab"],
                  n_servers=mix["deployment"]["servers"])
    dep = harness.Deployment(harness.arch_config(c), c, mix, seed)
    dep.start()
    dep.warm()
    drv = harness.drive(dep, mix, reqs, 3.0)
    dep.shutdown()
    chk = harness.check(c, seed, drv["sent"], 48, control=True)
    limit = c["limits"]["max_logit_gap"]
    assert chk["max_logit_gap"] <= limit < chk["control_logit_gap"], chk


def test_expert_readers():
    rec = {"counters": {"steps": 10, "expert_picks": 10 * 26 * 8 * 18,
                        "expert_peak_picks": 10 * 26 * 27},
           "work": {"expert_layers": 26, "experts_held": 8}}
    load = harness.load_module("metrics", "expert_load").value(rec)
    imb = harness.load_module("metrics", "expert_imbalance").value(rec)
    assert load == pytest.approx(18.0)
    assert imb == pytest.approx(150.0)
    bare = {"counters": {"steps": 10}, "work": {}}
    for m in ("expert_load", "expert_imbalance"):
        assert harness.load_module("metrics", m).value(bare) is None


def test_cell_reads_its_expert_metrics():
    c, mix = shrink(conf(), T.load_mix("solo.batch192"))
    out, _, rec = harness.run_cell(CELL, 2**31 + 31, 3.0, False,
                                   t_start=time.monotonic(), bench=BENCHMARK,
                                   conf=c, mix=mix)
    assert out["correct"], out["check"]
    load = harness.load_module("metrics", "expert_load").value(rec)
    imb = harness.load_module("metrics", "expert_imbalance").value(rec)
    # every row of the 4 is routed, and picks an expert at most once: a
    # held expert takes 4 x 6 / 64 picks a step on average, at most 4; the
    # busiest held expert takes at least the mean
    assert 0 < load <= 4
    assert imb >= 100
    listed = {m["name"] for m in harness.metric_names(BENCHMARK, CELL, True)}
    assert {"expert_load", "expert_imbalance"} <= listed
