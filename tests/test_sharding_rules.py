"""Unit + property tests for the logical-axis sharding rules.

The shape/axes property sweep uses hypothesis when installed; otherwise a
seeded-random fallback covers the same domain so nothing silently skips
(the dry-run integration test asserts a skip-free run of this file).
"""

import random

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.analysis.hlo import (
    CollectiveOp,
    collective_bytes_per_device,
    parse_collectives,
)
from repro.launch.mesh import make_mesh
from repro.runtime.sharding import DEFAULT_RULES, Sharder, logical_to_spec


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs >=8 devices (run under dry-run env)")
    return make_mesh((2, 4), ("data", "model"))


def mk_mesh():
    n = jax.device_count()
    if n < 8:
        pytest.skip("needs >=8 devices")
    return make_mesh((2, 4), ("data", "model"))


def test_basic_mapping():
    mesh = mk_mesh()
    spec = logical_to_spec((64, 128), ("embed", "mlp"), mesh)
    assert spec == P("data", "model")


def test_auto_drop_non_divisible():
    mesh = mk_mesh()
    # 6 kv heads on a 4-way model axis -> replicated
    spec = logical_to_spec((64, 6, 16), ("embed", "kv_heads", None), mesh)
    assert spec == P("data")
    # batch=1 cannot shard
    spec = logical_to_spec((1, 128), ("batch", None), mesh)
    assert spec == P()


def test_no_axis_reuse_within_tensor():
    mesh = mk_mesh()
    # both dims prefer "model": second one must drop it
    spec = logical_to_spec((8, 8), ("mlp", "heads"), mesh)
    assert spec == P("model")


def test_multi_axis_batch():
    if jax.device_count() < 8:
        pytest.skip("needs >=8 devices")
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    spec = logical_to_spec((8, 16), ("batch", None), mesh3)
    assert spec == P(("pod", "data"))


def test_partial_multi_axis_when_divisibility_limits():
    if jax.device_count() < 8:
        pytest.skip("needs >=8 devices")
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    # dim 2 divisible by pod(2) but not pod*data(4)
    spec = logical_to_spec((2, 16), ("batch", None), mesh3)
    assert spec == P("pod")


_LOGICAL_AXES = [None, "batch", "embed", "mlp", "heads", "kv_heads",
                 "vocab", "experts", "act_seq"]


def _check_spec_valid(dims):
    """Property: any (shape, axes) resolves to a spec whose mesh axes are
    unique and divide the corresponding dims."""
    mesh = mk_mesh()
    shape = tuple(d for d, _ in dims)
    axes = tuple(a for _, a in dims)
    spec = logical_to_spec(shape, axes, mesh)
    seen = set()
    for i, part in enumerate(tuple(spec)):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        prod = 1
        for m in parts:
            assert m not in seen
            seen.add(m)
            prod *= mesh.shape[m]
        assert shape[i] % prod == 0


if HAVE_HYPOTHESIS:

    @settings(max_examples=50, deadline=None)
    @given(
        hst.lists(
            hst.tuples(
                hst.integers(1, 512),
                hst.sampled_from(_LOGICAL_AXES),
            ),
            min_size=1,
            max_size=4,
        )
    )
    def test_spec_always_valid(dims):
        _check_spec_valid(dims)

else:

    @pytest.mark.parametrize("seed", range(50))
    def test_spec_always_valid(seed):
        rng = random.Random(seed)
        dims = [
            (rng.randint(1, 512), rng.choice(_LOGICAL_AXES))
            for _ in range(rng.randint(1, 4))
        ]
        _check_spec_valid(dims)


def test_sharder_noop_without_mesh():
    s = Sharder(None)
    x = np.ones((4, 4))
    assert s.constrain(x, "batch", None) is x


# --------------------------------------------------------------------------- #
# distributed-optimization helpers (run under the 8-fake-device subprocess)
# --------------------------------------------------------------------------- #
def test_quantize_roundtrip():
    import jax.numpy as jnp

    from repro.runtime.dist import dequantize_int8, quantize_int8

    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 3.0,
                    jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(s) * 0.51)


def test_compressed_psum_matches_fp32():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.runtime.dist import compressed_psum

    if jax.device_count() < 8:
        pytest.skip("needs >=8 devices")
    mesh = make_mesh((8,), ("pod",))
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(8, 128)), jnp.float32
    )
    f = jax.shard_map(lambda a: compressed_psum(a, "pod"), mesh=mesh,
                  in_specs=P("pod"), out_specs=P("pod"))
    got = np.asarray(f(x))
    want = np.asarray(x.sum(0, keepdims=True))
    # every shard holds the (quantized) global sum
    for i in range(8):
        np.testing.assert_allclose(got[i], want[0], atol=0.2, rtol=0.05)


def test_topk_error_feedback_conserves_mass():
    import jax.numpy as jnp

    from repro.runtime.dist import topk_compress

    g = jnp.asarray(np.random.default_rng(2).normal(size=(256,)), jnp.float32)
    err = jnp.zeros_like(g)
    sparse, new_err = topk_compress(g, err, frac=0.1)
    # decomposition is exact
    np.testing.assert_allclose(np.asarray(sparse + new_err), np.asarray(g),
                               rtol=1e-6)
    assert int((np.asarray(sparse) != 0).sum()) <= 26 + 5  # ~top 10% (+ties)


def test_gpipe_matches_sequential():
    import jax.numpy as jnp

    from repro.runtime.pipeline import gpipe_forward

    if jax.device_count() < 8:
        pytest.skip("needs >=8 devices")
    mesh = make_mesh((4,), ("pipe",))
    # stage i: y = x * w_i + i-agnostic bias stored in params
    ws = jnp.asarray([[1.5], [0.5], [2.0], [1.0]], jnp.float32)  # [4,1]

    def stage(w, x):
        return x * w[0]

    xs = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)  # 6 microbatches
    out = gpipe_forward(mesh, stage, ws, xs, axis="pipe")
    want = xs * 1.5 * 0.5 * 2.0 * 1.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


# --------------------------------------------------------------------------- #
# HLO collective parsing
# --------------------------------------------------------------------------- #
HLO_SAMPLE = """
  %all-reduce = f32[32,128]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  ROOT %ag = bf16[4,256]{1,0} all-gather(%p), channel_id=2, replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
  %rs = f32[8]{0} reduce-scatter(%x), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = u32[16]{0} collective-permute(%y), channel_id=4, source_target_pairs={{0,1}}
  %nota = f32[2]{0} add(%a, %b)
"""


def test_parse_collectives_kinds_and_bytes():
    ops = parse_collectives(HLO_SAMPLE)
    kinds = [o.kind for o in ops]
    assert kinds == ["all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute"]
    ar, ag, rs, cp = ops
    assert ar.out_bytes == 32 * 128 * 4 and ar.group_size == 4
    assert ag.out_bytes == 4 * 256 * 2 and ag.group_size == 2
    assert rs.out_bytes == 8 * 4 and rs.group_size == 4
    assert cp.out_bytes == 16 * 4


def test_collective_traffic_model():
    ar = CollectiveOp("all-reduce", 1000, 4)
    assert ar.traffic_bytes == pytest.approx(2 * 3 / 4 * 1000)
    rs = CollectiveOp("reduce-scatter", 100, 8)
    assert rs.traffic_bytes == pytest.approx(7 * 100)
    assert CollectiveOp("all-gather", 100, 1).traffic_bytes == 0.0


def test_traffic_summary():
    s = collective_bytes_per_device(HLO_SAMPLE)
    assert s["n_ops"] == 4
    assert s["total_traffic_bytes"] > 0
    assert set(s["by_kind"]) == {"all-reduce", "all-gather",
                                 "reduce-scatter", "collective-permute"}
