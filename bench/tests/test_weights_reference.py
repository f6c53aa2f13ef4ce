"""The benchmark's weights and its plain reference, against the program
at a small size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.harness import load_module

SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 96, "vocab": 256, "rope_theta": 10000.0,
         "norm_eps": 1e-5, "swa_window": None, "param_dtype": "float32",
         "compute_dtype": "float32", "max_batch": 2, "max_len": 32,
         "arch": "smollm_360m", "reference": "llama_dense"}


def test_a_layer_made_alone_equals_its_stacked_slice():
    seed = 2**31 + 999  # past 32 signed bits
    full = W.served(SMALL, seed, 1, "float32")
    base = W.base_key(seed, 1)
    # jitted, as the reference makes it (eager rounds the scale apart)
    one = jax.jit(lambda b, l: W.layer(SMALL, b, l, jnp.float32))(base, 1)
    np.testing.assert_array_equal(full["layers"]["mlp"]["down"][1],
                                  one["down"])
    np.testing.assert_array_equal(full["layers"]["attn"]["wq"][1], one["wq"])
    assert not np.array_equal(full["layers"]["attn"]["wq"][0], one["wq"])


def test_reference_agrees_with_the_programs_forward():
    """Same weights, float32 compute: a sequence extended greedily by the
    program's full-sequence forward reads a gap of ~0 in the reference at
    every generated position, and the float8 control does not."""
    from bench.harness import arch_config
    from repro.models.registry import build_model
    from repro.runtime.sharding import Sharder

    ref = load_module("reference", "llama_dense")
    model = build_model(arch_config(SMALL))
    params = W.served(SMALL, 5, 0, "float32")
    fwd = jax.jit(lambda p, t, q: model.forward(
        p, {"tokens": t, "positions": q}, Sharder(None))[0])
    seq = np.zeros((1, 32), np.int32)
    seq[0, :4] = [7, 100, 3, 42]
    pos = np.arange(32, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        for t in range(3, 31):
            seq[0, t + 1] = int(jnp.argmax(fwd(params, seq, pos)[0, t]))
    served, ctl = ref.gaps(SMALL, 5, 0, seq, control=True)
    assert served.shape == (1, 31)
    assert served[0, 3:].max() < 1e-4
    assert ctl[0, 3:].max() > 10 * max(served[0, 3:].max(), 1e-4)
