"""The harness takes a configuration's architecture by name, from its
family module: the dense family reads exactly what it read when the harness
made its weights and its work count itself, a family the benchmark has no
module for is served, counted and rehearsed through the harness with no
edit, and the run record carries the program's counters for the per-layer
metrics."""

import copy
import json
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import weights as W
from bench.harness import BENCH, ROOT
from bench.tests import toy_moe
from bench.tests.test_rehearsal import BENCHMARK, CELLS, small, shrink

DENSE = harness.load_module("reference", "llama_dense")
CONFIGS = ("smollm_360m", "h2o_danube3_4b")


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# -- the dense maker and work count as they were before the family modules,
#    kept to show that the move changed no number -------------------------

_OLD_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "gate", "up", "down")
_OLD_TOP = ("embed", "final_norm", "unembed")
_OLD_PATHS = {
    "ln1": ("layers", "ln1"), "ln2": ("layers", "ln2"),
    "wq": ("layers", "attn", "wq"), "wk": ("layers", "attn", "wk"),
    "wv": ("layers", "attn", "wv"), "wo": ("layers", "attn", "wo"),
    "gate": ("layers", "mlp", "gate"), "up": ("layers", "mlp", "up"),
    "down": ("layers", "mlp", "down"),
    "embed": ("embed", "tok"), "final_norm": ("final_norm",),
    "unembed": ("unembed",),
}


def _old_shapes(c):
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


def _old_leaf(key, shape, fan, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if fan is None:
        x = 1.0 + 0.05 * x
    elif fan == "embed":
        x = 0.02 * x
    else:
        x = x * (1.0 / math.sqrt(fan))
    return x.astype(dtype)


def _old_key(base, name):
    return jax.random.fold_in(base, (_OLD_LAYER + _OLD_TOP).index(name))


def _old_served(c, seed, server, dtype):
    sh = _old_shapes(c)

    def layer(base, l):
        return {n: _old_leaf(jax.random.fold_in(_old_key(base, n), l),
                             *sh[n], dtype) for n in _OLD_LAYER}

    @jax.jit
    def make(base):
        flat = jax.vmap(lambda l: layer(base, l))(jnp.arange(c["n_layers"]))
        flat.update({n: _old_leaf(_old_key(base, n), *sh[n], dtype)
                     for n in _OLD_TOP})
        out: dict = {}
        for name, x in flat.items():
            *head, last = _OLD_PATHS[name]
            d = out
            for h in head:
                d = d.setdefault(h, {})
            d[last] = x
        return out

    return make(W.base_key(seed, server))


def _old_serve_step(c):
    d, H, KV, hd, ff, V, L = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                              c["head_dim"], c["d_ff"], c["vocab"],
                              c["n_layers"])
    total = 2 * V * d + d + L * (2 * d + d * H * hd * 2 + d * KV * hd * 2
                                 + 3 * d * ff)
    B, T = c["max_batch"], c["max_len"]
    win = min(c.get("swa_window") or T, T)
    size = {"float32": 4, "bfloat16": 2}
    wb, cb = size[c["param_dtype"]], size[c["compute_dtype"]]
    mm = total - V * d
    weight_bytes = mm * wb + B * d * wb
    cache_bytes = L * (2 * B * win * KV * hd * cb + B * win * 4)
    return {"flops": 2.0 * B * mm + L * B * 2 * (2 * H * hd * win),
            "bytes": float(weight_bytes + cache_bytes),
            "weight_bytes": float(weight_bytes),
            "cache_bytes": float(cache_bytes)}


@pytest.mark.parametrize("name", CONFIGS)
def test_served_is_bit_identical_to_the_old_dense_maker(name):
    c = dict(DENSE.small(conf(name)), vocab=96, d_ff=48)
    seed = 2**31 + 41
    new = harness.family(c).served(c, seed, 1, c["param_dtype"])
    old = _old_served(c, seed, 1, c["param_dtype"])
    assert jax.tree_util.tree_structure(new) == \
        jax.tree_util.tree_structure(old)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype == jnp.dtype(c["param_dtype"])
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", CONFIGS)
def test_serve_step_is_the_old_count(name):
    c = conf(name)
    assert harness.family(c).serve_step(c) == _old_serve_step(c)


# -- a family the benchmark has no module for ------------------------------

@pytest.fixture
def toy(monkeypatch, tmp_path):
    """BENCHMARK.json with one more configuration and cell, whose
    ``reference`` names the toy module; ``load_module`` finds that module
    as it would find ``bench/reference/toy_moe.py``."""
    file = tmp_path / "toy_moe.json"
    file.write_text(json.dumps(toy_moe.CONF))
    bench = copy.deepcopy(BENCHMARK)
    bench["configs"].append({"name": "toy_moe", "file": str(file)})
    bench["workloads"].append({"name": "toy_moe.solo", "config": "toy_moe",
                               "traffic": "solo.batch32", "chips": 1})
    load = harness.load_module

    def load_module(kind, name):
        if (kind, name) == ("reference", "toy_moe"):
            return toy_moe
        return load(kind, name)

    monkeypatch.setattr(harness, "load_module", load_module)
    return bench


def test_toy_family_is_served_counted_and_rehearsed(toy):
    _, c, mix = harness.cell_spec(toy, "toy_moe.solo")
    c, mix = shrink(c, mix)
    out, chk, rec = harness.run_cell(
        "toy_moe.solo", 2**31 + 3, 3.0, False, t_start=time.monotonic(),
        bench=toy, conf=c, mix=mix)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert chk["tokens"] >= 48
    assert rec["work"] == toy_moe.serve_step(c)
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_module_without_the_exports_is_an_error(monkeypatch):
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name: types.SimpleNamespace(gaps=None))
    with pytest.raises(AttributeError, match="served, serve_step"):
        harness.family({"reference": "bare"})


# -- the program's counters in the run record ------------------------------

NEW = ("batch_occupancy", "loop_host_ms", "window_compiles",
       "held_wait_share", "queue_wait_p95_ms", "prefill_p95_s")


@pytest.mark.parametrize("cell", CELLS)
def test_record_carries_program_counters(cell):
    c, mix = small(cell)
    out, _, rec = harness.run_cell(
        cell, 2**31 + 29, 3.0, False, t_start=time.monotonic(),
        bench=BENCHMARK, conf=c, mix=mix)
    assert out["correct"], out["check"]
    cnt = rec["counters"]
    for k in ("steps", "rows", "host_s", "device_wait_s", "compiles",
              "admitted", "finished", "wait_s", "dispatches", "cojob_done"):
        assert cnt[k] >= 0, k
    assert cnt["steps"] > 0 and cnt["rows"] <= cnt["steps"] * c["max_batch"]
    assert rec["servers"] == mix["deployment"]["servers"]
    assert rec["max_batch"] == c["max_batch"]
    n = rec["client"]["attempted"]
    assert len(rec["server"]["queue_wait_s"]) == \
        len(rec["server"]["prefill_s"]) == n

    got = {m: harness.load_module("metrics", m).value(rec) for m in NEW}
    assert 0 < got["batch_occupancy"] <= 100
    assert got["loop_host_ms"] > 0
    assert got["window_compiles"] == 0  # every program warmed up
    assert 0 <= got["held_wait_share"] <= 100
    ttft = harness.load_module("metrics", "ttft_p95_s").value(rec)
    # a request's queue wait and prefill are parts of its first-token time
    assert 0 <= got["queue_wait_p95_ms"] / 1e3 <= ttft
    assert 0 < got["prefill_p95_s"] <= ttft
    listed = {m["name"] for m in harness.metric_names(BENCHMARK, cell, True)}
    assert listed >= set(NEW) - {"held_wait_share"}


def test_new_readers_find_nothing_without_work():
    rec = {"counters": {"steps": 0}, "server": {"queue_wait_s": [],
                                                "prefill_s": []}}
    for m in ("batch_occupancy", "loop_host_ms", "queue_wait_p95_ms",
              "prefill_p95_s"):
        assert harness.load_module("metrics", m).value(rec) is None


def test_harness_names_no_family():
    src = (BENCH / "harness.py").read_text()
    for word in ("n_kv_heads", "d_ff", "head_dim", "llama", "dense",
                 *CONFIGS):
        assert word not in src, word
    assert (ROOT / "bench" / "reference" / "llama_dense.py").exists()
