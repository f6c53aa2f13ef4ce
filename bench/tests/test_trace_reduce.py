"""The trace reduction and the trace metrics' readers, pinned on a small
trace recorded on one TPU v5e: ``bench/data/small.xplane.pb.gz`` is a
quarter-second slice (events outside it dropped, the window span cut to
it) of a 4 s traced window of two co-located ``smollm_360m`` servers and
the host BLAS job. It holds 9 serve-step executions."""

import json

import pytest

from bench import trace_reduce as R
from bench.harness import BENCH, family, load_module
from bench.peaks import peaks

SMALL = BENCH / "data" / "small.xplane.pb.gz"
PIN = json.loads((BENCH / "data" / "small.pin.json").read_text())


def test_union_and_cover():
    assert R.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert R.covered([(0, 2), (3, 4)], 1, 3.5) == 1.5


@pytest.fixture(scope="module")
def reduced():
    return R.reduce(SMALL)


def test_pinned_numbers(reduced):
    assert reduced["devices"] == 1 and len(reduced["steps_s"]) == 9
    for k in ("window_s", "busy_s"):
        assert reduced[k] == pytest.approx(PIN[k], rel=1e-9)
    assert reduced["steps_s"] == pytest.approx(PIN["steps_s"], rel=1e-9)
    assert reduced["step_gaps_s"] == pytest.approx(PIN["step_gaps_s"],
                                                   rel=1e-9, abs=1e-12)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10


def test_trace_metrics_read_the_trace(reduced):
    conf = json.loads((BENCH / "configs" / "smollm_360m.json").read_text())
    rec = {"trace": reduced, "work": family(conf).serve_step(conf),
           "peak": peaks("TPU v5 lite")}
    got = {m: load_module("metrics", m).value(rec)
           for m in ("host_gap_ms", "serve_step_roofline", "step_mfu",
                     "device_idle_share")}
    steps = PIN["steps_s"]
    assert got["host_gap_ms"] == pytest.approx(
        1e3 * sum(PIN["step_gaps_s"]) / len(PIN["step_gaps_s"]))
    bound = rec["work"]["bytes"] / 819e9  # memory-bound at this shape
    assert got["serve_step_roofline"] == pytest.approx(
        100 * bound / (sum(steps) / len(steps)))
    assert got["step_mfu"] == pytest.approx(
        100 * len(steps) * rec["work"]["flops"] / (PIN["window_s"] * 197e12))
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - PIN["busy_s"] / PIN["window_s"]))
    for v in got.values():
        assert 0 < v < 100


def test_readers_find_nothing_without_a_trace():
    rec = {"trace": None}
    for m in ("host_gap_ms", "serve_step_roofline", "step_mfu",
              "device_idle_share"):
        assert load_module("metrics", m).value(rec) is None
