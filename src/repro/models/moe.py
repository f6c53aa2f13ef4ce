"""Mixture-of-Experts layer (deepseek-moe, grok-1).

Design (GShard-style, TPU-native):

* top-k routing with **per-row capacity** (groups = batch rows, which align
  with the data shards — so position-in-expert cumsums never cross shards).
* token dispatch via **batched scatter** into an [rows, E, C, d] buffer
  (never materializes the [T, E, C] one-hot tensor, which is astronomically
  large at pod scale); combine via batched gather.
  ``moe_impl="onehot"`` provides the classic einsum dispatch for small
  shapes / cross-checking.
* expert FFNs computed with the experts dim sharded over the model axis
  when divisible (EP: GSPMD inserts the all-to-all at the x_e constraint);
  otherwise expert weights shard over (embed->data, mlp->model) like dense
  weights (grok: 8 experts on a 16-way axis).
* optional shared experts (deepseek: 2 shared + 64 routed top-6).
* load-balancing aux loss (Switch/GShard form) + router z-loss.
* an expert share (``cfg.expert_parallel`` chips split the experts): the
  router keeps all ``n_experts`` outputs, this chip holds and computes
  ``cfg.experts_held`` of them, and a pick of another chip's expert adds
  nothing here. The serve path of such a layer is ``moe_share_decode``:
  dropless, with its picks counted (``holds_share``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.base import ParamSpec
from repro.models.layers import mlp, mlp_specs


def moe_specs(cfg) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    held = cfg.experts_held
    specs = {
        "router": ParamSpec((d, E), ("embed", "experts"), init="fan_in",
                            dtype="float32"),
        "wg": ParamSpec((held, d, ff), ("experts", "embed", "mlp"),
                        init="fan_in"),
        "wu": ParamSpec((held, d, ff), ("experts", "embed", "mlp"),
                        init="fan_in"),
        "wd": ParamSpec((held, ff, d), ("experts", "mlp", "embed"),
                        init="fan_in"),
    }
    if cfg.n_shared_experts:
        specs["shared"] = mlp_specs(d, cfg.n_shared_experts * ff, "silu")
    return specs


def _capacity(tokens_per_row: int, n_experts: int, top_k: int, cf: float) -> int:
    c = int(tokens_per_row * top_k * cf / n_experts) + 1
    return max(4, min(c, tokens_per_row * top_k))


def _route(params, cfg, x):
    """Router logits, probabilities and the top-k picks (fp32): ``gates``
    and ``eidx`` [..., K], the gates renormalised only where
    ``cfg.norm_topk_prob``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )
    return logits, probs, gate_vals, eidx


def moe_block(params: dict, cfg, sharder, x: jax.Array,
              *, impl: str = "scatter") -> tuple[jax.Array, dict]:
    """x: [B, S, d] -> (y [B, S, d], aux losses). With an expert share,
    the held experts (ids 0 .. experts_held-1) take their picks within the
    capacity; picks of other experts take no capacity and add nothing."""
    B, S, d = x.shape
    E_all, K = cfg.n_experts, cfg.top_k
    E = cfg.experts_held
    share = holds_share(cfg)
    C = _capacity(S, E_all, K, cfg.capacity_factor)
    dt = x.dtype

    # ---- routing (fp32) ------------------------------------------------- #
    logits, probs, gate_vals, eidx = _route(params, cfg, x)  # [B,S,K]

    # ---- aux losses ------------------------------------------------------ #
    me = probs.mean(axis=(0, 1))                        # [E] mean router prob
    ce = jnp.zeros((E_all,), jnp.float32)
    for j in range(K):
        ce = ce + jax.nn.one_hot(eidx[..., j], E_all,
                                 dtype=jnp.float32).mean((0, 1))
    ce = ce / K
    aux_loss = cfg.moe_aux_loss * E_all * jnp.sum(me * ce)
    z_loss = 1e-3 * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    if share:
        # a pick of an expert not held here becomes the id E, which
        # one_hot makes a zero row: it takes no capacity
        mine = eidx < E
        eidx = jnp.where(mine, eidx, E)

    # ---- position-in-expert (per row: cumsums stay shard-local) ---------- #
    pos_list, keep_list = [], []
    counts = jnp.zeros((B, E), jnp.int32)
    for j in range(K):
        oh = jax.nn.one_hot(eidx[..., j], E, dtype=jnp.int32)      # [B,S,E]
        pos_full = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos = jnp.take_along_axis(
            pos_full, jnp.minimum(eidx[..., j], E - 1)[..., None], axis=-1
        )[..., 0]                                                   # [B,S]
        keep = pos < C
        if share:
            keep = keep & mine[..., j]
        pos_list.append(pos)
        keep_list.append(keep)
        counts = counts + oh.sum(axis=1)
    pos_k = jnp.stack(pos_list, axis=-1)    # [B,S,K]
    keep_k = jnp.stack(keep_list, axis=-1)  # [B,S,K]

    if impl == "scatter":
        # dispatch: batched scatter-add into [B, E, C, d]
        eidx_f = jnp.where(keep_k, eidx, E)         # ->dropped
        pos_f = jnp.where(keep_k, pos_k, C)

        def row_dispatch(xr, er, pr):
            # xr [S,d]; er,pr [S,K]
            buf = jnp.zeros((E, C, d), dt)
            xs = jnp.repeat(xr[:, None, :], K, axis=1).reshape(S * K, d)
            return buf.at[er.reshape(-1), pr.reshape(-1)].add(
                xs, mode="drop"
            )

        x_e = jax.vmap(row_dispatch)(x, eidx_f, pos_f)   # [B,E,C,d]
    else:  # onehot (reference; small shapes only)
        disp = jnp.zeros((B, S, E, C), jnp.float32)
        for j in range(K):
            oh_e = jax.nn.one_hot(eidx[..., j], E, dtype=jnp.float32)
            oh_c = jax.nn.one_hot(pos_k[..., j], C, dtype=jnp.float32)
            disp = disp + (
                oh_e[..., None] * oh_c[..., None, :]
                * keep_k[..., j][..., None, None]
            )
        x_e = jnp.einsum("bsec,bsd->becd", disp, x.astype(jnp.float32)).astype(dt)

    x_e = sharder.constrain(x_e, "act_batch", "act_experts", None, None)

    # ---- expert FFNs (SwiGLU) -------------------------------------------- #
    g = jnp.einsum("becd,edf->becf", x_e, params["wg"].astype(dt))
    u = jnp.einsum("becd,edf->becf", x_e, params["wu"].astype(dt))
    h = jax.nn.silu(g) * u
    h = sharder.constrain(h, "act_batch", "act_experts", None, "act_mlp")
    out_e = jnp.einsum("becf,efd->becd", h, params["wd"].astype(dt))
    out_e = sharder.constrain(out_e, "act_batch", "act_experts", None, None)

    # ---- combine ----------------------------------------------------------- #
    if impl == "scatter":
        def row_combine(oer, er, pr, gr):
            # oer [E,C,d]; er,pr,gr [S,K]
            flat = oer.reshape(E * C, d)
            idx = er * C + pr
            idx = jnp.where(idx < E * C, idx, E * C - 1)
            vals = flat[idx.reshape(-1)].reshape(S, K, d)
            return jnp.einsum("skd,sk->sd", vals, gr.astype(dt))

        gates_masked = jnp.where(keep_k, gate_vals, 0.0)
        y = jax.vmap(row_combine)(out_e, eidx_f, pos_f, gates_masked)
    else:
        # combine weights: dispatch one-hots weighted by gates
        cw = jnp.zeros((B, S, E, C), jnp.float32)
        for j in range(K):
            oh_e = jax.nn.one_hot(eidx[..., j], E, dtype=jnp.float32)
            oh_c = jax.nn.one_hot(pos_k[..., j], C, dtype=jnp.float32)
            cw = cw + (
                oh_e[..., None] * oh_c[..., None, :]
                * (gate_vals[..., j] * keep_k[..., j])[..., None, None]
            )
        y = jnp.einsum("bsec,becd->bsd", cw, out_e.astype(jnp.float32)).astype(dt)

    # ---- shared experts (deepseek) ------------------------------------------ #
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x, "silu", sharder)

    return y, {"moe_aux": aux_loss, "moe_z": z_loss}


#: what ``moe_share_decode`` counts, per step
COUNTS = ("expert_picks", "expert_peak_picks")


def holds_share(cfg) -> bool:
    """Whether this chip holds only part of each layer's routed experts.
    Its decode then runs ``moe_share_decode``, dropless and counted; a
    chip holding every expert decodes through ``moe_block``'s capacity
    layer, as it always has."""
    return cfg.experts_held < cfg.n_experts


def moe_share_decode(params: dict, cfg, x: jax.Array, *,
                     rows: jax.Array | None = None
                     ) -> tuple[jax.Array, dict]:
    """The serve path of an expert share, dropless: x [B, S, d] (the
    batch's tokens, one group) -> (y [B, S, d], counts). The share is
    experts ``0 .. experts_held - 1`` of the router's. ``rows`` [B * S]
    marks the tokens whose picks are counted (None: every token); it
    changes no output.

    The router picks over all ``n_experts``; each held expert's capacity
    is every token of the group (a token picks an expert at most once), so
    its slots are the tokens themselves, in order, with no dispatch: it
    computes each token and weights it by the token's gate for it, 0
    where the token did not pick it. Shared experts run on every token.

    ``counts``: ``expert_picks``, the counted tokens' picks landing on
    the held experts, and ``expert_peak_picks``, those of the busiest held
    expert (int32 scalars)."""
    B, S, d = x.shape
    E = cfg.experts_held
    dt = x.dtype
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        _, _, gate_vals, eidx = _route(params, cfg, xt)          # [N,K]
        picked = eidx[..., None] == jnp.arange(E)                # [N,K,E]
        w = jnp.where(picked, gate_vals[..., None], 0.0).sum(1)  # [N,E]
        counted = picked if rows is None else picked & rows[:, None, None]
        per_expert = counted.sum((0, 1), dtype=jnp.int32)        # [E]
        counts = {"expert_picks": per_expert.sum(),
                  "expert_peak_picks": per_expert.max()}
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("nd,edf->enf", xt, params["wg"].astype(dt))
        u = jnp.einsum("nd,edf->enf", xt, params["wu"].astype(dt))
        out = jnp.einsum("enf,efd->end", jax.nn.silu(g) * u,
                         params["wd"].astype(dt))
        y = jnp.einsum("end,ne->nd", out, w.astype(dt)).reshape(B, S, d)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + mlp(params["shared"], x, "silu")
    return y, counts
