"""The yardstick's arithmetic against hand counts."""

import json

import pytest

from bench import peaks, work
from bench.harness import BENCH


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_param_counts_match_hand_arithmetic():
    # smollm: 2 x 49152 x 960 (untied) + 32 x (2*960 + 2*960*15*64
    # + 2*960*5*64 + 3*960*2560) + 960 = 409.0M
    assert work.params(conf("smollm_360m"))["total"] == 409_007_040
    # danube: 2 x 32000 x 3840 + 24 x (2*3840 + 2*3840*32*120
    # + 2*3840*8*120 + 3*3840*10240) + 3840 = 3.96B
    assert work.params(conf("h2o_danube3_4b"))["total"] == 3_961_839_360


def test_params_match_the_programs_own_tree():
    from repro.models.base import param_count
    from repro.models.registry import build_param_specs
    from bench.harness import arch_config

    for name in ("smollm_360m", "h2o_danube3_4b"):
        c = conf(name)
        assert param_count(build_param_specs(arch_config(c))) == \
            work.params(c)["total"]


def test_serve_step_bytes_and_flops():
    s = work.serve_step(conf("smollm_360m"))
    # f32 weights except the gathered embedding, plus 32 gathered rows
    assert s["weight_bytes"] == (409_007_040 - 49152 * 960) * 4 + 32 * 960 * 4
    # bf16 k and v: 32 layers x 32 rows x 768 x 5 x 64 x 2 B x 2,
    # plus the int32 position table
    assert s["cache_bytes"] == 32 * (2 * 32 * 768 * 5 * 64 * 2
                                     + 32 * 768 * 4)
    assert s["bytes"] == pytest.approx(2.46e9, rel=0.01)
    d = work.serve_step(conf("h2o_danube3_4b"))
    assert d["weight_bytes"] == pytest.approx(7.68e9, rel=0.01)
    assert d["cache_bytes"] == pytest.approx(2.27e9, rel=0.01)
    # 2 x 32 x 3.839e9 matrix weights + 24 x 32 x 4 x 32 x 120 x 768
    assert d["flops"] == 2 * 32 * (3_961_839_360 - 32000 * 3840) \
        + 24 * 32 * 4 * 32 * 120 * 768
    assert d["flops"] == pytest.approx(254.75e9, rel=0.001)


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
