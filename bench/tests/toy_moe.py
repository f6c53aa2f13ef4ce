"""A toy family module for the harness's tests, of an architecture the
benchmark has no module for: the program's mixture-of-experts model
(``deepseek_moe_16b``'s family: a dense first layer, then routed and
shared experts) at a CPU's size.

It exports what ``bench/reference/<name>.py`` exports, so the tests can
show that the harness serves, counts, checks and rehearses it without an
edit. Unlike a family module of the benchmark, it reads its tree from the
program's parameter specs and takes the program's own full-sequence
forward as its reference: a stand-in for the plumbing, never a yardstick.
Its capacity factor leaves room for every token in the forward pass, so
the forward routes as the one-token decode step does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench import work

CONF = {
    "arch": "deepseek_moe_16b", "reference": "toy_moe",
    "n_layers": 2, "first_k_dense": 1, "d_model": 32, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 8, "d_ff": 64, "vocab": 256,
    "n_experts": 4, "n_shared_experts": 1, "top_k": 2, "expert_d_ff": 16,
    "capacity_factor": 4.0, "rope_theta": 10000.0, "norm_eps": 1e-6,
    "swa_window": None, "tie_embeddings": False,
    "param_dtype": "float32", "compute_dtype": "float32",
    "max_batch": 4, "max_len": 128,
    "limits": {"max_logit_gap": 0.3, "wrong_lengths": 0, "unanswered": 0},
}


def _model(c: dict):
    from bench.harness import arch_config
    from repro.models.registry import build_model

    return build_model(arch_config(c))


def _leaves(c: dict):
    from repro.models.base import is_spec

    return jax.tree_util.tree_flatten(_model(c).param_specs(),
                                      is_leaf=is_spec)


def served(c: dict, seed: int, server: int, dtype) -> dict:
    specs, treedef = _leaves(c)

    @jax.jit
    def make(base):
        out = []
        for i, s in enumerate(specs):
            fan = ("embed" if s.init == "embed" else
                   None if s.init == "ones" or len(s.shape) < 2 else
                   s.shape[-2])
            out.append(W.leaf(jax.random.fold_in(base, i), s.shape, fan,
                              s.dtype or dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(W.base_key(seed, server))


def serve_step(c: dict) -> dict:
    """Every weight but the embedding read once, ``2 x max_batch``
    operations each, and a key and value cache read whole."""
    specs, _ = _leaves(c)
    n = sum(int(np.prod(s.shape)) for s in specs) - c["vocab"] * c["d_model"]
    B, T = c["max_batch"], c["max_len"]
    cache = (c["n_layers"] * 2 * B * T * c["n_kv_heads"] * c["head_dim"]
             * work.BYTES[c["compute_dtype"]])
    weights = n * work.BYTES[c["param_dtype"]]
    return {"flops": 2.0 * B * n, "bytes": float(weights + cache),
            "weight_bytes": float(weights), "cache_bytes": float(cache)}


def gaps(c: dict, seed: int, server: int, seqs: np.ndarray, *,
         control: bool = False):
    from repro.runtime.sharding import Sharder

    model = _model(c)
    params = served(c, seed, server, c["param_dtype"])
    pos = np.broadcast_to(np.arange(seqs.shape[1], dtype=np.int32),
                          seqs.shape)
    with jax.default_matmul_precision("highest"):
        lg, _ = model.forward(params, {"tokens": jnp.asarray(seqs),
                                       "positions": jnp.asarray(pos)},
                              Sharder(None))
    lg = np.asarray(lg, np.float32)[:, :-1]
    nxt = np.take_along_axis(lg, seqs[:, 1:, None], -1)[..., 0]
    return lg.max(-1) - nxt, None  # no control


def small(c: dict, *, full_width: bool = False) -> dict:
    """Already a CPU's size."""
    return dict(c)
