"""Model assembly for all 10 assigned architectures.

One ``LM`` class covers every family; per-family *blocks* are composed and
run under ``lax.scan`` over stacked layer parameters (constant-size HLO at
any depth) with a configurable remat policy.

Families:
  dense  — [ln → GQA attn → +res] [ln → (SwiGLU|GeLU) MLP → +res]
  moe    — dense block with the FFN replaced by the MoE layer
           (+ optional leading dense layers: deepseek first_k_dense)
  ssm    — [ln → mamba2 mixer → +res]
  hybrid — Griffin pattern (rec, rec, local-attn) scanned as superblocks
           + unrolled remainder blocks; every temporal block is followed
           by its MLP block
  vlm    — dense with M-RoPE positions [3,B,S]; patch-embedding frontend
           stub (assignment: modality frontend provides embeddings)
  audio  — encoder-only dense: bidirectional attention, GeLU FFN, frame
           embedding frontend stub
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models.base import ParamSpec, is_spec


# --------------------------------------------------------------------------- #
# remat policies
# --------------------------------------------------------------------------- #
def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    raise ValueError(f"unknown remat mode {mode}")


def _stack_specs(specs: Any, n: int) -> Any:
    return jax.tree_util.tree_map(lambda s: s.stacked(n), specs, is_leaf=is_spec)


def _maybe_scan(cfg, f, init, xs):
    """lax.scan over stacked layers, or a Python unroll when
    cfg.scan_layers is False (used by the dry-run's per-layer cost probes —
    XLA's cost analysis counts a while-loop body once regardless of trip
    count, so probes must be unrolled)."""
    if cfg.scan_layers:
        return jax.lax.scan(f, init, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for i in range(n):
        xi = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = f(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


# --------------------------------------------------------------------------- #
# block definitions
# --------------------------------------------------------------------------- #
def dense_block_specs(cfg, *, attn_window: Optional[int], d_ff: Optional[int] = None):
    return {
        "ln1": L.rmsnorm_spec(cfg.d_model),
        "attn": attn.attn_specs(cfg),
        "ln2": L.rmsnorm_spec(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, d_ff or cfg.d_ff, cfg.mlp_act),
    }


def moe_block_specs(cfg):
    return {
        "ln1": L.rmsnorm_spec(cfg.d_model),
        "attn": attn.attn_specs(cfg),
        "ln2": L.rmsnorm_spec(cfg.d_model),
        "moe": moe_mod.moe_specs(cfg),
    }


def ssm_block_specs(cfg):
    return {"ln": L.rmsnorm_spec(cfg.d_model), "mixer": m2.mamba2_specs(cfg)}


def rec_block_specs(cfg):
    return {
        "ln1": L.rmsnorm_spec(cfg.d_model),
        "rec": rg.rglru_specs(cfg),
        "ln2": L.rmsnorm_spec(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
    }


def _res(sharder, x):
    # residual-stream layout is THE sharding lever of the §Perf iterations:
    # act_seq->model = Megatron-SP; act_embed->model = activation TP layout
    return sharder.constrain(x, "act_batch", "act_seq", "act_embed")


def _attn_fn(p, cfg, sharder, positions, mode, window):
    fn = lambda h: attn.attention_block(p, cfg, sharder, h, positions,
                                        mode=mode, window=window)
    if cfg.remat_attention:
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def dense_block_fwd(p, cfg, sharder, x, positions, *, mode, window):
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = sharder.sp_boundary(h)  # explicit bf16 seq all-gather (iteration E)
    h = _attn_fn(p["attn"], cfg, sharder, positions, mode, window)(h)
    x = _res(sharder, x + h)
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    h = sharder.sp_boundary(h)
    h = L.mlp(p["mlp"], h, cfg.mlp_act, sharder)
    return _res(sharder, x + h)


def moe_block_fwd(p, cfg, sharder, x, positions, *, mode, window):
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = sharder.sp_boundary(h)
    h = _attn_fn(p["attn"], cfg, sharder, positions, mode, window)(h)
    x = _res(sharder, x + h)
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    # iteration I: gather the seq dim BEFORE routing — otherwise each
    # model shard dispatches only its seq slice and the dispatch buffers
    # get all-reduced over the model axis (15 GB/layer/device on grok)
    h = sharder.sp_boundary(h)
    h, aux = moe_mod.moe_block(p["moe"], cfg, sharder, h)
    return _res(sharder, x + h), aux


def ssm_block_fwd(p, cfg, sharder, x):
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    h = m2.mamba2_block(p["mixer"], cfg, sharder, h)
    return _res(sharder, x + h)


def rec_block_fwd(p, cfg, sharder, x):
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = rg.rglru_block(p["rec"], cfg, sharder, h)
    x = _res(sharder, x + h)
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    h = L.mlp(p["mlp"], h, cfg.mlp_act, sharder)
    return _res(sharder, x + h)


# --------------------------------------------------------------------------- #
# the LM
# --------------------------------------------------------------------------- #
class LM:
    def __init__(self, cfg):
        self.cfg = cfg

    # ---------------- param specs ---------------- #
    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {}
        if cfg.frontend == "token":
            specs["embed"] = L.embed_specs(cfg.vocab, cfg.d_model)
        else:
            d_in = cfg.frontend_dim or cfg.d_model
            specs["frontend"] = {"proj": L.frontend_proj_spec(d_in, cfg.d_model)}
        specs["final_norm"] = L.rmsnorm_spec(cfg.d_model)
        specs["unembed"] = L.unembed_spec(cfg.d_model, cfg.vocab)

        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            specs["layers"] = _stack_specs(
                dense_block_specs(cfg, attn_window=cfg.swa_window), cfg.n_layers
            )
        elif fam == "moe":
            k = cfg.first_k_dense
            if k:
                specs["dense_layers"] = _stack_specs(
                    dense_block_specs(cfg, attn_window=None), k
                )
            specs["layers"] = _stack_specs(moe_block_specs(cfg), cfg.n_layers - k)
        elif fam == "ssm":
            specs["layers"] = _stack_specs(ssm_block_specs(cfg), cfg.n_layers)
        elif fam == "hybrid":
            n_super, n_tail = self._hybrid_split()
            specs["superblocks"] = _stack_specs(
                {
                    "rec1": rec_block_specs(cfg),
                    "rec2": rec_block_specs(cfg),
                    "attn": dense_block_specs(cfg, attn_window=cfg.local_window),
                },
                n_super,
            )
            specs["tail"] = {
                str(i): rec_block_specs(cfg) for i in range(n_tail)
            }
        else:
            raise ValueError(f"unknown family {fam}")
        return specs

    def _hybrid_split(self) -> tuple[int, int]:
        n_super = self.cfg.n_layers // 3
        n_tail = self.cfg.n_layers - 3 * n_super
        return n_super, n_tail

    # ---------------- embedding in / out ---------------- #
    def _embed_in(self, params, batch, sharder):
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        if cfg.frontend == "token":
            x = L.embed(batch["tokens"], params["embed"]["tok"], cdt)
        else:
            x = L.frontend_proj(batch["embeds"].astype(cdt),
                                params["frontend"]["proj"])
        return sharder.constrain(x, "act_batch", "act_seq", None)

    def _logits_out(self, params, x, sharder):
        cfg = self.cfg
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(x, params["unembed"])
        return sharder.constrain(logits, "act_batch", None, "act_vocab")

    # ---------------- full-sequence forward (train / prefill) ---------------- #
    def forward(self, params, batch, sharder) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = self._embed_in(params, batch, sharder)
        positions = batch["positions"]
        aux = {"moe_aux": jnp.zeros((), jnp.float32),
               "moe_z": jnp.zeros((), jnp.float32)}
        mode = "bidir" if cfg.encoder_only else "causal"

        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            body = _remat(
                lambda p, h: dense_block_fwd(p, cfg, sharder, h, positions,
                                             mode=mode, window=cfg.swa_window),
                cfg.remat,
            )
            x, _ = _maybe_scan(cfg, lambda c, p: (body(p, c), None), x,
                               params["layers"])
        elif fam == "moe":
            if cfg.first_k_dense:
                dense_body = _remat(
                    lambda p, h: dense_block_fwd(p, cfg, sharder, h, positions,
                                                 mode=mode, window=None),
                    cfg.remat,
                )
                x, _ = _maybe_scan(cfg, lambda c, p: (dense_body(p, c), None), x,
                                   params["dense_layers"])

            moe_body = _remat(
                lambda p, h: moe_block_fwd(p, cfg, sharder, h, positions,
                                           mode=mode, window=None),
                cfg.remat,
            )

            def fm(carry, p):
                x_c, aux_a, aux_z = carry
                x_n, a = moe_body(p, x_c)
                return (x_n, aux_a + a["moe_aux"], aux_z + a["moe_z"]), None

            (x, aux_a, aux_z), _ = _maybe_scan(
                cfg, fm, (x, aux["moe_aux"], aux["moe_z"]), params["layers"]
            )
            aux = {"moe_aux": aux_a, "moe_z": aux_z}
        elif fam == "ssm":
            body = _remat(lambda p, h: ssm_block_fwd(p, cfg, sharder, h),
                          cfg.remat)
            x, _ = _maybe_scan(cfg, lambda c, p: (body(p, c), None), x,
                               params["layers"])
        elif fam == "hybrid":
            def super_fwd(p, h):
                h = rec_block_fwd(p["rec1"], cfg, sharder, h)
                h = rec_block_fwd(p["rec2"], cfg, sharder, h)
                return dense_block_fwd(p["attn"], cfg, sharder, h, positions,
                                       mode="causal", window=cfg.local_window)

            body = _remat(super_fwd, cfg.remat)
            x, _ = _maybe_scan(cfg, lambda c, p: (body(p, c), None), x,
                               params["superblocks"])
            tail_body = _remat(lambda p, h: rec_block_fwd(p, cfg, sharder, h),
                               cfg.remat)
            for i in sorted(params["tail"], key=int):
                x = tail_body(params["tail"][i], x)
        else:
            raise ValueError(fam)

        return self._logits_out(params, x, sharder), aux

    # ---------------- decode ---------------- #
    def cache_specs(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        fam = cfg.family
        if not cfg.supports_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode cache")
        if fam in ("dense", "vlm"):
            per = attn.cache_specs(cfg, batch, max_len, window=cfg.swa_window)
            return {"layers": _stack_specs(per, cfg.n_layers)}
        if fam == "moe":
            per = attn.cache_specs(cfg, batch, max_len, window=None)
            out = {"layers": _stack_specs(per, cfg.n_layers - cfg.first_k_dense)}
            if cfg.first_k_dense:
                out["dense_layers"] = _stack_specs(per, cfg.first_k_dense)
            return out
        if fam == "ssm":
            return {"layers": _stack_specs(m2.mamba2_cache_specs(cfg, batch),
                                           cfg.n_layers)}
        if fam == "hybrid":
            n_super, n_tail = self._hybrid_split()
            per_attn = attn.cache_specs(cfg, batch, max_len,
                                        window=cfg.local_window)
            per_rec = rg.rglru_cache_specs(cfg, batch)
            return {
                "superblocks": _stack_specs(
                    {"rec1": per_rec, "rec2": per_rec, "attn": per_attn}, n_super
                ),
                "tail": {str(i): rg.rglru_cache_specs(cfg, batch)
                         for i in range(n_tail)},
            }
        raise ValueError(fam)

    def decode_step(self, params, cache, tokens, positions, sharder):
        """One token for every row. tokens [B] (or embeds [B,1,Din]);
        positions [B] (or [3,B] for vlm). Returns (logits [B,V], cache)."""
        cfg = self.cfg
        self._sharder = sharder
        cdt = jnp.dtype(cfg.compute_dtype)
        if cfg.frontend == "token":
            x = L.embed(tokens[:, None], params["embed"]["tok"], cdt)
        else:
            x = L.frontend_proj(tokens.astype(cdt), params["frontend"]["proj"])

        fam = cfg.family
        if fam in ("dense", "vlm"):
            x, c = self._attn_decode_scan(params["layers"], cache["layers"],
                                          x, positions)
            new_cache = {"layers": c}
        elif fam == "moe":
            new_cache = {}
            if cfg.first_k_dense:
                x, new_cache["dense_layers"] = self._attn_decode_scan(
                    params["dense_layers"], cache["dense_layers"], x, positions)
            x, new_cache["layers"] = self._attn_decode_scan(
                params["layers"], cache["layers"], x, positions)
        elif fam == "ssm":
            def body_s(carry, xs):
                p, c = xs
                h = L.rmsnorm(carry, p["ln"], cfg.norm_eps)
                h, c2 = m2.mamba2_decode(p["mixer"], cfg, sharder, h, c)
                return carry + h, c2

            x, nl = _maybe_scan(cfg, body_s, x, (params["layers"], cache["layers"]))
            new_cache = {"layers": nl}
        elif fam == "hybrid":
            # the recurrent states have no position axis: they stay the
            # scan's xs/ys; the attention cache rides in the carry
            def body_h(carry, xs):
                y, ca, i = carry
                p, c = xs
                y, c1 = self._rec_decode_block(p["rec1"], c["rec1"], y)
                y, c2 = self._rec_decode_block(p["rec2"], c["rec2"], y)
                y, ca = self._attn_decode_block(
                    p["attn"], ca, i, y, positions, window=cfg.local_window
                )
                return (y, ca, i + 1), {"rec1": c1, "rec2": c2}

            sb = cache["superblocks"]
            (x, ca, _), nsb = _maybe_scan(
                cfg, body_h, (x, sb["attn"], jnp.int32(0)),
                (params["superblocks"], {"rec1": sb["rec1"], "rec2": sb["rec2"]}),
            )
            nsb["attn"] = ca
            new_tail = {}
            for i in sorted(params["tail"], key=int):
                x, ct = self._rec_decode_block(
                    params["tail"][i], cache["tail"][i], x
                )
                new_tail[i] = ct
            new_cache = {"superblocks": nsb, "tail": new_tail}
        else:
            raise ValueError(fam)

        logits = self._logits_out(params, x, sharder)[:, 0]
        return logits, new_cache

    # decode block helpers ------------------------------------------------- #
    def _attn_decode_scan(self, params, cache, x, positions):
        """Decode through a stack of attention blocks. The stacked cache
        and the layer index ride in the scan's carry, so each block
        writes its slot in place and the cache stays one buffer in one
        layout; only the parameters are scanned over."""
        def body(carry, p):
            y, c, i = carry
            y, c = self._attn_decode_block(p, c, i, y, positions)
            return (y, c, i + 1), None

        (x, cache, _), _ = _maybe_scan(
            self.cfg, body, (x, cache, jnp.int32(0)), params)
        return x, cache

    def _attn_decode_block(self, p, c, layer, x, positions, *, window=None):
        cfg = self.cfg
        win = window if window is not None else cfg.swa_window
        pos_b = positions if positions.ndim == 1 else positions[0]
        sharder = self._sharder
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        h, c2 = attn.attention_decode(
            p["attn"], cfg, sharder, h, c, layer,
            positions if cfg.mrope_sections else pos_b, window=win,
        )
        x = x + h
        h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if "mlp" in p:
            h = L.mlp(p["mlp"], h, cfg.mlp_act, sharder)
        else:
            # decode-time MoE: route the whole batch as ONE group ([B,1,d]
            # -> [1,B,d]) so expert capacity is shared across rows instead
            # of a per-row floor — removes the ~30x dead-slot compute of
            # per-row capacity at S=1 (§Perf iteration H).
            hh = jnp.swapaxes(h, 0, 1)
            hh, _ = moe_mod.moe_block(p["moe"], cfg, sharder, hh)
            h = jnp.swapaxes(hh, 0, 1)
        return x + h, c2

    def _rec_decode_block(self, p, c, x):
        cfg = self.cfg
        sharder = self._sharder
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        h, c2 = rg.rglru_decode(p["rec"], cfg, sharder, h, c)
        x = x + h
        h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        h = L.mlp(p["mlp"], h, cfg.mlp_act, sharder)
        return x + h, c2

    # decode needs the sharder on self (scan bodies take fixed signatures)
    _sharder = None

    def bind_sharder(self, sharder) -> "LM":
        self._sharder = sharder
        return self
