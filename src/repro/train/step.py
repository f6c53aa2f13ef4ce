"""Train / eval steps: microbatch gradient accumulation, remat, optimizer.

``make_train_step`` builds the function handed to ``jax.jit`` in both the
real trainer and the dry-run. Gradient reduction across data/pod axes is
GSPMD's job (params are sharded/replicated by the in_shardings; XLA emits
the reduce-scatter/all-reduce and overlaps it with the backward when the
latency-hiding scheduler allows); microbatching bounds activation memory
with a scan whose carry is the fp32 grad accumulator.

Preemption: nothing in these factories checkpoints, deliberately — a
``usf.checkpoint()`` cannot run inside a traced function (it would
execute once at trace time, then never again). The preemption point for
a jitted step is its *call site*: the trainer wraps the jitted function
with ``repro.core.autockpt`` and the serving engine checkpoints before
each step dispatch, so every dispatch boundary checkpoints
(docs/PREEMPTION.md tier 3).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.optim import make_optimizer
from repro.optim.schedules import warmup_cosine
from repro.train.loss import lm_loss


def init_train_state(model, params) -> dict:
    opt = make_optimizer(model.cfg.optimizer)
    return {"step": jnp.zeros((), jnp.int32), "params": params,
            "opt": opt.init(params)}


def _loss_fn(model, sharder, params, batch):
    logits, aux = model.forward(params, batch, sharder)
    loss, metrics = lm_loss(logits, batch["labels"], z_loss=model.cfg.z_loss)
    if model.cfg.family == "moe":
        loss = loss + aux["moe_aux"] + aux["moe_z"]
        metrics["moe_aux"] = aux["moe_aux"]
    metrics["loss"] = loss
    return loss, metrics


def _split_microbatches(batch: dict, k: int) -> dict:
    def rs(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] % k == 0:
            return x.reshape((k, x.shape[0] // k) + x.shape[1:])
        if hasattr(x, "ndim") and x.ndim >= 2:  # [3,B,S] positions (vlm)
            return x.reshape(
                (x.shape[0], k, x.shape[1] // k) + x.shape[2:]
            ).swapaxes(0, 1)
        raise ValueError(f"cannot split microbatch on {getattr(x, 'shape', x)}")

    return jax.tree_util.tree_map(rs, batch)


def make_train_step(
    model,
    sharder,
    *,
    microbatches: int = 1,
    peak_lr: float = 3e-4,
    warmup: int = 100,
    total_steps: int = 10_000,
    accum_dtype: str = "float32",
) -> Callable[[dict, dict], tuple[dict, dict]]:
    opt = make_optimizer(model.cfg.optimizer)
    adt = jnp.dtype(accum_dtype)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: _loss_fn(model, sharder, p, batch), has_aux=True
            )(params)
        else:
            mb = _split_microbatches(batch, microbatches)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, adt), params
            )

            def acc(carry, mbatch):
                gsum = carry
                (l, m), g = jax.value_and_grad(
                    lambda p: _loss_fn(model, sharder, p, mbatch), has_aux=True
                )(params)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(adt), gsum, g
                )
                return gsum, (l, m)

            grads, (losses, mlist) = jax.lax.scan(acc, g0, mb)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
            metrics = jax.tree_util.tree_map(lambda x: x.mean(0), mlist)
            loss = losses.mean()

        lr = warmup_cosine(state["step"], peak_lr=peak_lr, warmup=warmup,
                           total=total_steps)
        new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads))
        )
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        return (
            {"step": state["step"] + 1, "params": new_params, "opt": new_opt},
            metrics,
        )

    return train_step


def make_eval_step(model, sharder) -> Callable[[dict, dict], dict]:
    def eval_step(params: dict, batch: dict) -> dict:
        _, metrics = _loss_fn(model, sharder, params, batch)
        return metrics

    return eval_step


def make_prefill_step(model, sharder) -> Callable[[dict, dict], jax.Array]:
    """Full-sequence forward (inference prefill): logits only."""

    def prefill_step(params: dict, batch: dict) -> jax.Array:
        logits, _ = model.forward(params, batch, sharder)
        return logits

    return prefill_step


#: the serve step's name, pinned: its jitted module is ``jit_serve_step``
#: and its operations sit under the ``serve_step`` scope, whatever the
#: function is called, so that a trace finds the step by this name
SERVE_STEP = "serve_step"


def make_serve_step(model, sharder
                    ) -> Callable[..., tuple[jax.Array, dict, dict]]:
    """One decode token against a KV cache: (logits, cache, counts), as
    ``LM.decode_step`` returns them."""

    def step(params: dict, cache: dict, tokens: jax.Array,
             positions: jax.Array, rows: jax.Array | None = None
             ) -> tuple[jax.Array, dict, dict]:
        with jax.named_scope(SERVE_STEP):
            return model.decode_step(params, cache, tokens, positions,
                                     sharder, rows)

    step.__name__ = step.__qualname__ = SERVE_STEP
    return step
