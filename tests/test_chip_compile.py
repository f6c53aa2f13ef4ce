"""Compile the serving path's programs for a described TPU v5e chip at
real widths. Nothing runs: the TPU compiler is installed here and refuses
what the chip would refuse (misaligned blocks, unsupported ops, programs
that do not fit its memory), at no chip time.

Only one process at a time may load libtpu, and every xdist worker imports
every test file, so the topology is described inside a module fixture and
never at import, in a ``skipif``, in ``parametrize`` or in conftest. All
such compiles live in this one file: a second file could land on another
worker, whose fixture would skip in silence.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
from repro.models.base import abstract_tree
from repro.models.registry import build_model
from repro.runtime.sharding import Sharder
from repro.train.step import make_serve_step

HBM_BYTES = 16 * 1024**3  # one v5e chip
# chip_smoke.py's batch and cache length (MAX_BATCH, MAX_LEN)
SMOKE_BATCH, SMOKE_LEN = 8, 128
# the benchmark's serving shape (``max_batch``, ``max_len`` in
# bench/configs/), and each configuration's stored weight dtype there
BENCH_BATCH, BENCH_LEN = 32, 768
BENCH_ARCHS = {"smollm_360m": "float32", "h2o_danube_3_4b": "bfloat16"}
# the step's temporaries at that shape when it wrote the cache by a scatter
# into the scan's per-layer slice and returned the cache as the scan's
# output: relayouts around the scatter, and a second stacked cache
SCATTER_TEMP_BYTES = {"smollm_360m": 1_649_926_144,
                      "h2o_danube_3_4b": 2_362_139_648}


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent cache
    off: a compile for a described chip is written to it but cannot be
    read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    ma = compiled.memory_analysis()
    resident = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return 0 < resident < HBM_BYTES


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert _fits(compiled)
    return compiled


def _kernel_compiles(fn, *args):
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel


@pytest.fixture(scope="module")
def smollm():
    cfg = get_arch("smollm_360m")
    return cfg, build_model(cfg)


@pytest.fixture(scope="module")
def serve_step(chip, smollm):
    """The full-width decode step the smoke serves, cache donated."""
    cfg, model = smollm
    params = _on(abstract_tree(model.param_specs(), cfg.param_dtype), chip)
    cache = _on(abstract_tree(model.cache_specs(SMOKE_BATCH, SMOKE_LEN),
                              cfg.param_dtype), chip)
    tok = _spec((SMOKE_BATCH,), jnp.int32, chip)
    return jax.jit(make_serve_step(model, Sharder(None)),
                   donate_argnums=(1,)).lower(params, cache, tok, tok).compile()


def test_serve_step_compiles(serve_step):
    assert _fits(serve_step)


def test_serve_step_module_keeps_its_name(serve_step):
    """Traces find the step by its module's name and its ops' scope."""
    text = serve_step.as_text()
    assert text.startswith("HloModule jit_serve_step")
    assert 'op_name="jit(serve_step)/serve_step/' in text


@pytest.fixture(scope="module", params=sorted(BENCH_ARCHS))
def bench_step(request, chip):
    """The donated decode step at the benchmark's shape: (arch, abstract
    cache, compiled step)."""
    arch = request.param
    cfg = dataclasses.replace(get_arch(arch), param_dtype=BENCH_ARCHS[arch])
    model = build_model(cfg)
    params = _on(abstract_tree(model.param_specs(), cfg.param_dtype), chip)
    cache = _on(abstract_tree(model.cache_specs(BENCH_BATCH, BENCH_LEN),
                              cfg.param_dtype), chip)
    tok = _spec((BENCH_BATCH,), jnp.int32, chip)
    step = _compile(make_serve_step(model, Sharder(None)), params, cache,
                    tok, tok, donate_argnums=(1,))
    return arch, cache, step


def _squeezed(shape) -> tuple:
    return tuple(d for d in shape if d != 1)


def test_bench_step_copies_no_cache(bench_step):
    """No copy of the stacked cache or of one layer of it: the cache stays
    in one buffer and one layout through the layer scan."""
    _, cache, step = bench_step
    cache_shapes = set()
    for leaf in jax.tree_util.tree_leaves(cache):
        cache_shapes |= {_squeezed(leaf.shape), _squeezed(leaf.shape[1:])}
    copies = []
    for m in re.finditer(r"%(\S+) = \w+\[([\d,]*)\]\S* copy\(",
                         step.as_text()):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if _squeezed(dims) in cache_shapes:
            copies.append(m.group(0))
    assert not copies


def test_bench_step_aliases_cache(bench_step):
    """The donated cache is updated in place: every byte of it aliases an
    output."""
    _, cache, step = bench_step
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert step.memory_analysis().alias_size_in_bytes == cache_bytes


def test_bench_step_temporaries_halved(bench_step):
    arch, _, step = bench_step
    temp = step.memory_analysis().temp_size_in_bytes
    assert temp < SCATTER_TEMP_BYTES[arch] / 2


def test_reference_forward_compiles(chip, smollm):
    """The full-width ``LM.forward`` the smoke checks answers against."""
    cfg, model = smollm
    params = _on(abstract_tree(model.param_specs(), cfg.param_dtype), chip)
    seq = _spec((1, SMOKE_LEN), jnp.int32, chip)
    _compile(lambda p, t, q: model.forward(
        p, {"tokens": t, "positions": q}, Sharder(None))[0], params, seq, seq)


def test_flash_attention_compiles(chip, smollm):
    cfg, _ = smollm
    S = 2048  # SmolLM's context length
    q = _spec((1, S, cfg.n_heads, cfg.hd), jnp.bfloat16, chip)
    kv = _spec((1, S, cfg.n_kv_heads, cfg.hd), jnp.bfloat16, chip)
    _kernel_compiles(lambda a, b, c: ops.flash_attention(a, b, c), q, kv, kv)


def test_decode_attention_compiles_batched(chip, smollm):
    """B=8 over a 2048-slot cache: several kv blocks per row."""
    cfg, _ = smollm
    B, W = 8, 2048
    q = _spec((B, cfg.n_heads, cfg.hd), jnp.bfloat16, chip)
    kv = _spec((B, W, cfg.n_kv_heads, cfg.hd), jnp.bfloat16, chip)
    _kernel_compiles(ops.flash_decode, q, kv, kv,
                     _spec((B, W), jnp.int32, chip),
                     _spec((B,), jnp.int32, chip))


def test_moe_gmm_compiles(chip):
    cfg = get_arch("deepseek_moe_16b")
    E, C = cfg.n_experts, 128
    _kernel_compiles(ops.moe_gmm,
                     _spec((E, C, cfg.d_model), jnp.bfloat16, chip),
                     _spec((E, cfg.d_model, cfg.expert_d_ff), jnp.bfloat16,
                           chip))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Pallas TPU lowering: 'the last two dimensions of your block shape are "
    "divisible by 8 and 128 respectively, or be equal to the respective "
    "dimensions of the overall array' -- the dt block (1, 1, Q) over "
    "[B, H, S] (ROADMAP D5)"))
def test_ssd_scan_compiles(chip):
    cfg = get_arch("mamba2_2_7b")
    B, S = 1, 2048
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    N = cfg.ssm_state
    _kernel_compiles(
        lambda *a: ops.ssd_scan(*a, chunk=cfg.ssm_chunk),
        _spec((B, S, H, cfg.ssm_head_dim), jnp.bfloat16, chip),
        _spec((B, S, H), jnp.float32, chip), _spec((H,), jnp.float32, chip),
        _spec((B, S, N), jnp.bfloat16, chip),
        _spec((B, S, N), jnp.bfloat16, chip))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=(
    "Pallas TPU lowering: 'Unimplemented primitive in Pallas TPU lowering "
    "for KernelType.TC: dynamic_slice' -- the fori_loop over time steps "
    "(ROADMAP D5)"))
def test_rglru_scan_compiles(chip):
    W = get_arch("recurrentgemma_9b").lru_width
    B, S = 1, 2048
    ab = _spec((B, S, W), jnp.bfloat16, chip)
    _kernel_compiles(ops.rglru, ab, ab, _spec((B, W), jnp.float32, chip))
