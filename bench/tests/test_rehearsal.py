"""The harness end to end on the CPU, through its own functions: every
traffic mix at a small size, the comparison that decides ``correct``
against the float8 control and a planted fault, and the CLI's refusal to
run without a TPU."""

import copy
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.harness import BENCH, ROOT

BENCHMARK = harness.load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def small(cell, *, full_width=False):
    """The cell's files cut to a CPU's size: two layers (and, unless
    ``full_width``, tiny widths) as the family's module cuts them, 4 rows
    of 128 positions, short requests, a slow arrival rate."""
    _, conf, mix = harness.cell_spec(BENCHMARK, cell)
    return shrink(conf, mix, full_width=full_width)


def shrink(conf, mix, *, full_width=False):
    """Sizes from the configuration's family module (``small``), 4 rows of
    128 positions, and the mix cut to fit."""
    conf = dict(harness.family(conf).small(conf, full_width=full_width),
                max_batch=4, max_len=128)
    mix = copy.deepcopy(mix)
    mix["prompt_len"].update(median=12, min=4, max=64)
    mix["output_len"].update(median=8, min=4, max=60)
    if mix["arrivals"]["loop"] == "open":
        mix["arrivals"]["rate_per_s"] = 8
    else:
        mix["arrivals"].update(clients=4, pool=16)
    if mix["deployment"].get("cojob"):
        mix["deployment"]["cojob"]["n"] = 64
    mix["check"]["sample_tokens"] = 48
    return conf, mix


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell):
    conf, mix = small(cell)
    out, chk, _ = harness.run_cell(cell, 2**31 + 17, 3.0, False,
                                   t_start=time.monotonic(), bench=BENCHMARK,
                                   conf=conf, mix=mix)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in harness.metric_names(BENCHMARK, cell, False)}
    assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert list(out)[-1] == "check"
    assert chk["tokens"] >= 48 and chk["wrong_lengths"] == 0
    if mix["arrivals"]["loop"] == "open":
        # exactly the planned arrivals, all due inside the window
        assert out["attempted"] == round(mix["arrivals"]["rate_per_s"] * 3)


#: each configuration file with the traffic mix it is served under
MIX_OF = {"smollm_360m": "coloc2.hostblas", "h2o_danube3_4b": "solo.batch32"}


@pytest.mark.parametrize("config", sorted(MIX_OF))
def test_float8_control_fails_the_limit(config):
    """At full width (two layers), the program reads under its limit and
    the float8 control, at the same prompts and tokens, over it."""
    from bench import traffic as T

    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf, mix = shrink(conf, T.load_mix(MIX_OF[config]), full_width=True)
    seed = 23
    reqs = T.plan(mix, seed=seed, seconds=3.0, vocab=conf["vocab"],
                  n_servers=mix["deployment"]["servers"])
    dep = harness.Deployment(harness.arch_config(conf), conf, mix, seed)
    dep.start()
    dep.warm()
    drv = harness.drive(dep, mix, reqs, 3.0)
    dep.stop_cojob()
    dep.shutdown()
    chk = harness.check(conf, seed, drv["sent"], 48, control=True)
    limit = conf["limits"]["max_logit_gap"]
    assert chk["max_logit_gap"] <= limit < chk["control_logit_gap"], chk


def _alter_tokens(dep):
    """A token altered where it is produced: every fifth step serves
    token 7 in every row."""
    for s in dep.servers:
        step, n = s._step, [0]

        def bad(params, cache, toks, pos, _step=step, _n=n):
            logits, cache = _step(params, cache, toks, pos)
            _n[0] += 1
            if _n[0] % 5 == 0:
                logits = logits.at[:, 7].add(1e4)
            return logits, cache

        s._step = bad


def _stale_cache(dep):
    """A step that returns its state unchanged: the KV cache it was
    given, so no position is ever remembered. (The step donates its
    cache, so it is handed a copy.)"""
    import jax
    import jax.numpy as jnp

    for s in dep.servers:
        def bad(params, cache, toks, pos, _step=s._step):
            logits, _ = _step(params, jax.tree_util.tree_map(jnp.copy, cache),
                              toks, pos)
            return logits, cache

        s._step = bad


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(cell):
    conf, mix = small(cell, full_width=True)
    out, _, _ = harness.run_cell(cell, 11, 3.0, False,
                                 t_start=time.monotonic(), bench=BENCHMARK,
                                 conf=conf, mix=mix, fault=_alter_tokens)
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_stale_cache_is_not_correct(cell):
    conf, mix = small(cell, full_width=True)
    out, _, _ = harness.run_cell(cell, 13, 3.0, False,
                                 t_start=time.monotonic(), bench=BENCHMARK,
                                 conf=conf, mix=mix, fault=_stale_cache)
    assert out["correct"] is False, out["check"]


def _one_server_stops(dep):
    """A server that stops serving after warm-up: its requests are taken
    in and never answered."""
    dep.servers[-1].stop()


@pytest.mark.parametrize("cell", CELLS)
def test_unanswered_requests_are_not_correct(cell, monkeypatch):
    monkeypatch.setattr(harness, "DRAIN_CAP_S", 2.0)
    conf, mix = small(cell)
    out, chk, _ = harness.run_cell(cell, 17, 3.0, False,
                                   t_start=time.monotonic(), bench=BENCHMARK,
                                   conf=conf, mix=mix, fault=_one_server_stops)
    assert chk["unanswered"] > 0 and out["failed"] > 0
    assert out["correct"] is False, out["check"]


def _cli(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_cli_refuses_without_a_tpu():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ runs nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0 and "not in this checkout" in r.stderr
    assert r.stdout.strip() == ""
