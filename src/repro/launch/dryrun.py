"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1_5_110b \
        --shape train_4k [--multi-pod] [--debug-mesh] [--no-probes]
    PYTHONPATH=src python -m repro.launch.dryrun --all

For every (arch x shape x mesh) cell this:
  1. lowers + compiles the PRODUCTION step (scan-over-layers, remat,
     microbatching) on the 16x16 pod mesh / 2x16x16 multi-pod mesh,
     printing ``compiled.memory_analysis()`` (proves it fits) and
     ``compiled.cost_analysis()``;
  2. compiles two small UNROLLED probe models (1- and 2-layer variants) to
     derive exact per-layer FLOPs/bytes/collective-traffic — necessary
     because XLA cost analysis counts a scanned while-body once regardless
     of trip count (verified empirically; see EXPERIMENTS.md §Dry-run);
  3. emits the three roofline terms per DESIGN.md §9 into
     results/dryrun/<arch>.<shape>.<mesh>.json.
"""

# The VERY FIRST lines, before ANY other import (jax locks the device count
# on first init):
import os

_DEV = os.environ.get("REPRO_DRYRUN_DEVICES", "512")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_DEV}"
).strip()

import argparse
import dataclasses
import json
import pathlib
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_bytes_per_device
from repro.analysis.roofline import HW, model_flops_analytic, roofline_terms
from repro.configs.base import SHAPES, cell_supported, get_arch, list_archs
from repro.launch import inputs as inp
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.base import abstract_tree, is_spec, shardings_tree
from repro.models.registry import build_model
from repro.optim import make_optimizer
from repro.optim.optimizers import _factored
from repro.runtime.sharding import Sharder
from repro.train.step import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
)


# --------------------------------------------------------------------------- #
# sharding trees for every argument
# --------------------------------------------------------------------------- #
def _repl(mesh):
    return NamedSharding(mesh, P())


def _batch_shardings(cfg, shape, sharder) -> dict:
    mesh = sharder.mesh
    out: dict[str, Any] = {}
    B, S = shape.global_batch, shape.seq_len

    def sh(shp, axes):
        return NamedSharding(mesh, sharder.spec(shp, axes))

    if cfg.frontend == "token":
        out["tokens"] = sh((B, S), ("batch", None))
    else:
        d_in = cfg.frontend_dim or cfg.d_model
        out["embeds"] = sh((B, S, d_in), ("batch", None, None))
    if cfg.mrope_sections is not None:
        out["positions"] = sh((3, B, S), (None, "batch", None))
    else:
        out["positions"] = sh((B, S), ("batch", None))
    if shape.kind == "train":
        out["labels"] = sh((B, S), ("batch", None))
    return out


def _opt_shardings(opt_name: str, specs, sharder):
    mesh = sharder.mesh

    def param_sh(s):
        return NamedSharding(mesh, sharder.spec(s.shape, s.axes))

    if opt_name == "adamw":
        tree = jax.tree_util.tree_map(param_sh, specs, is_leaf=is_spec)
        return {"m": tree, "v": tree, "count": _repl(mesh)}
    if opt_name == "adafactor":
        def fac(s):
            if _factored(s.shape):
                return {
                    "vr": NamedSharding(
                        mesh, sharder.spec(s.shape[:-1], s.axes[:-1])
                    ),
                    "vc": NamedSharding(
                        mesh,
                        sharder.spec(
                            s.shape[:-2] + s.shape[-1:],
                            s.axes[:-2] + s.axes[-1:],
                        ),
                    ),
                }
            return {"v": param_sh(s)}

        return {
            "f": jax.tree_util.tree_map(fac, specs, is_leaf=is_spec),
            "count": _repl(mesh),
        }
    raise ValueError(opt_name)


# --------------------------------------------------------------------------- #
# cell construction
# --------------------------------------------------------------------------- #
def build_cell(cfg, shape, mesh, *, microbatches: int = 1,
               rules: Optional[dict] = None, fsdp_gather: bool = False,
               explicit_sp: bool = False, accum_dtype: str = "float32"):
    """Returns (fn, abstract_args, in_shardings, out_shardings)."""
    sharder = Sharder(mesh, rules, fsdp_gather=fsdp_gather)
    sharder.explicit_sp = explicit_sp
    model = build_model(cfg)
    specs = model.param_specs()
    params_abs = abstract_tree(specs, cfg.param_dtype)
    params_sh = shardings_tree(specs, sharder, cfg.param_dtype)

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        state_abs = {"step": jax.ShapeDtypeStruct((), jnp.int32),
                     "params": params_abs, "opt": opt_abs}
        state_sh = {"step": _repl(mesh), "params": params_sh,
                    "opt": _opt_shardings(cfg.optimizer, specs, sharder)}
        batch_abs = inp.batch_specs(cfg, shape.global_batch, shape.seq_len,
                                    with_labels=True)
        batch_sh = _batch_shardings(cfg, shape, sharder)
        fn = make_train_step(model, sharder, microbatches=microbatches,
                             accum_dtype=accum_dtype)
        return fn, (state_abs, batch_abs), (state_sh, batch_sh), (state_sh, None)

    if shape.kind == "prefill":
        batch_abs = inp.batch_specs(cfg, shape.global_batch, shape.seq_len,
                                    with_labels=False)
        batch_sh = _batch_shardings(cfg, shape, sharder)
        fn = make_prefill_step(model, sharder)
        return fn, (params_abs, batch_abs), (params_sh, batch_sh), None

    if shape.kind == "decode":
        cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        cache_abs = abstract_tree(cache_specs, cfg.param_dtype)
        cache_sh = shardings_tree(cache_specs, sharder, cfg.param_dtype)
        B = shape.global_batch
        if cfg.frontend == "token":
            tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
            tok_sh = NamedSharding(mesh, sharder.spec((B,), ("batch",)))
        else:
            d_in = cfg.frontend_dim or cfg.d_model
            tok_abs = jax.ShapeDtypeStruct((B, 1, d_in),
                                           jnp.dtype(cfg.compute_dtype))
            tok_sh = NamedSharding(
                mesh, sharder.spec((B, 1, d_in), ("batch", None, None))
            )
        if cfg.mrope_sections is not None:
            pos_abs = jax.ShapeDtypeStruct((3, B), jnp.int32)
            pos_sh = NamedSharding(mesh, sharder.spec((3, B), (None, "batch")))
        else:
            pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
            pos_sh = NamedSharding(mesh, sharder.spec((B,), ("batch",)))
        fn = make_serve_step(model, sharder)
        return (
            fn,
            (params_abs, cache_abs, tok_abs, pos_abs),
            (params_sh, cache_sh, tok_sh, pos_sh),
            (None, cache_sh, None),
        )

    raise ValueError(shape.kind)


def _compile(cfg, shape, mesh, *, microbatches=1, rules=None,
             fsdp_gather=False, explicit_sp=False, accum_dtype="float32"):
    fn, args, in_sh, out_sh = build_cell(
        cfg, shape, mesh, microbatches=microbatches, rules=rules,
        fsdp_gather=fsdp_gather, explicit_sp=explicit_sp,
        accum_dtype=accum_dtype,
    )
    # donate the mutable aggregate (train state / KV cache): the output
    # aliases the input buffer, halving the step's resident footprint
    donate = (0,) if shape.kind == "train" else (
        (1,) if shape.kind == "decode" else ()
    )
    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=donate,
        ).lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    return compiled, {"lower_s": round(t_lower, 2),
                      "compile_s": round(t_compile, 2)}


def _cost(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        ),
        "hbm_capacity": int(HW["hbm_bytes"]),
    }


# --------------------------------------------------------------------------- #
# probe decomposition (per-layer exact costs)
# --------------------------------------------------------------------------- #
def probe_pair(cfg):
    """(cfgA, cfgB, multiplier): total = costA + multiplier x (costB - costA)."""
    if cfg.family == "moe":
        fk = cfg.first_k_dense
        a = dataclasses.replace(cfg, n_layers=fk + 1, scan_layers=False)
        b = dataclasses.replace(cfg, n_layers=fk + 2, scan_layers=False)
        return a, b, cfg.n_layers - fk - 1
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // 3
        tail = cfg.n_layers - 3 * n_super
        a = dataclasses.replace(cfg, n_layers=3 + tail, scan_layers=False)
        b = dataclasses.replace(cfg, n_layers=6 + tail, scan_layers=False)
        return a, b, n_super - 1
    a = dataclasses.replace(cfg, n_layers=1, scan_layers=False)
    b = dataclasses.replace(cfg, n_layers=2, scan_layers=False)
    return a, b, cfg.n_layers - 1


def _probe_costs(cfg, shape, mesh, *, rules=None,
                 fsdp_gather=False, explicit_sp=False) -> dict:
    """Probes always run microbatches=1: the microbatch accumulation loop is
    itself a scan, whose body XLA cost analysis counts once — total step
    cost is independent of the microbatch count, so mb=1 probes are exact."""
    cfg_a, cfg_b, mult = probe_pair(cfg)
    out = {}
    for tag, c in (("A", cfg_a), ("B", cfg_b)):
        compiled, times = _compile(c, shape, mesh, microbatches=1,
                                   rules=rules, fsdp_gather=fsdp_gather,
                                   explicit_sp=explicit_sp)
        cost = _cost(compiled)
        coll = collective_bytes_per_device(compiled.as_text())
        out[tag] = {
            "layers": c.n_layers,
            **cost,
            "coll_traffic": coll["total_traffic_bytes"],
            "coll_traffic_tpu": coll["total_traffic_bytes_tpu"],
            "coll_by_kind": coll["by_kind"],
            **times,
        }
    a, b = out["A"], out["B"]
    out["multiplier"] = mult
    out["derived"] = {
        "flops": a["flops"] + mult * (b["flops"] - a["flops"]),
        "bytes": a["bytes"] + mult * (b["bytes"] - a["bytes"]),
        "coll_traffic": a["coll_traffic"]
        + mult * (b["coll_traffic"] - a["coll_traffic"]),
        "coll_traffic_tpu": a["coll_traffic_tpu"]
        + mult * (b["coll_traffic_tpu"] - a["coll_traffic_tpu"]),
        "per_layer_flops": b["flops"] - a["flops"],
        "per_layer_bytes": b["bytes"] - a["bytes"],
        "per_layer_coll": b["coll_traffic"] - a["coll_traffic"],
    }
    return out


# --------------------------------------------------------------------------- #
# cell runner
# --------------------------------------------------------------------------- #
#: production microbatch counts for the train shape (memory-fit choice;
#: the full-model compile proves it via memory_analysis). Cost terms are
#: microbatch-independent (see _probe_costs).
TRAIN_MICROBATCHES = {
    "qwen1_5_110b": 8,
    "smollm_360m": 8,
    "command_r_plus_104b": 8,
    "h2o_danube_3_4b": 8,
    "mamba2_2_7b": 8,
    "deepseek_moe_16b": 8,
    "grok_1_314b": 8,
    "recurrentgemma_9b": 8,
    "qwen2_vl_7b": 8,
    "hubert_xlarge": 8,
}


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             probes: bool = True, debug_mesh: bool = False,
             microbatches: Optional[int] = None, rules: Optional[dict] = None,
             fsdp_gather: bool = False, remat: Optional[str] = None,
             explicit_sp: bool = False, param_dtype: Optional[str] = None,
             capacity_factor: Optional[float] = None,
             verbose: bool = True) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_arch(arch_id)
    if microbatches is None:
        microbatches = (TRAIN_MICROBATCHES.get(arch_id, 8)
                        if shape.kind == "train" else 1)
    if shape.kind in ("prefill", "decode"):
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")  # serving dtype
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=param_dtype)
    if capacity_factor:
        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)

    mesh_name = "debug" if debug_mesh else ("2x16x16" if multi_pod else "16x16")
    result: dict[str, Any] = {
        "arch": arch_id,
        "arch_name": cfg.name,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "microbatches": microbatches,
        "fsdp_gather": fsdp_gather,
        "explicit_sp": explicit_sp,
        "remat": cfg.remat,
        "param_dtype": cfg.param_dtype,
    }

    ok, reason = cell_supported(cfg, shape)
    if not ok:
        result["status"] = "skip"
        result["reason"] = reason
        if verbose:
            print(f"[dryrun] SKIP {arch_id} x {shape_name}: {reason}")
        return result

    mesh = (make_debug_mesh(multi_pod=multi_pod) if debug_mesh
            else make_production_mesh(multi_pod=multi_pod))
    chips = mesh.size

    try:
        compiled, times = _compile(cfg, shape, mesh,
                                   microbatches=microbatches, rules=rules,
                                   fsdp_gather=fsdp_gather,
                                   explicit_sp=explicit_sp)
        mem = _memory(compiled)
        cost_full = _cost(compiled)
        coll_full = collective_bytes_per_device(compiled.as_text())
        result["full"] = {**times, "memory": mem, "cost_raw": cost_full,
                          "collectives_raw": coll_full,
                          "note": "scan bodies counted once by XLA cost "
                                  "analysis; roofline uses probe-derived "
                                  "totals"}
        if verbose:
            print(f"[dryrun] {arch_id} x {shape_name} @ {mesh_name}: "
                  f"compile {times['compile_s']}s")
            print(f"  memory_analysis: {mem}")
            print(f"  cost_analysis(raw): {cost_full}")

        if probes:
            pr = _probe_costs(cfg, shape, mesh, rules=rules,
                              fsdp_gather=fsdp_gather,
                              explicit_sp=explicit_sp)
            result["probes"] = pr
            d = pr["derived"]
            terms = roofline_terms(
                flops_per_device=d["flops"],
                bytes_per_device=d["bytes"],
                coll_traffic_per_device=d["coll_traffic"],
                chips=chips,
                model_flops=model_flops_analytic(cfg, shape),
            )
            result["roofline"] = terms.as_dict()
            terms_tpu = roofline_terms(
                flops_per_device=d["flops"],
                bytes_per_device=d["bytes"],
                coll_traffic_per_device=d["coll_traffic_tpu"],
                chips=chips,
                model_flops=model_flops_analytic(cfg, shape),
            )
            result["roofline_tpu_corrected"] = terms_tpu.as_dict()
            if verbose:
                print(f"  roofline: compute={terms.compute_s:.4f}s "
                      f"memory={terms.memory_s:.4f}s "
                      f"collective={terms.collective_s:.4f}s "
                      f"dominant={terms.dominant} "
                      f"useful={terms.useful_flops_ratio:.2f} "
                      f"mfu_bound={terms.mfu_bound:.3f}")
        result["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] ERROR {arch_id} x {shape_name}: {result['error']}")
    return result


def _out_path(outdir: str, r: dict) -> pathlib.Path:
    p = pathlib.Path(outdir)
    p.mkdir(parents=True, exist_ok=True)
    return p / f"{r['arch']}.{r['shape']}.{r['mesh']}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--fsdp-gather", action="store_true",
                    help="use-time FSDP weight gathering (perf iteration D)")
    ap.add_argument("--explicit-sp", action="store_true",
                    help="explicit bf16 SP boundaries (perf iterations E/I)")
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--remat", choices=["none", "full", "dots"], default=None)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                for mp in meshes:
                    cells.append((a, s, mp))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    failures = 0
    for a, s, mp in cells:
        mesh_name = "debug" if args.debug_mesh else ("2x16x16" if mp else "16x16")
        path = pathlib.Path(args.out) / f"{a}.{s}.{mesh_name}.json"
        if args.skip_existing and path.exists():
            prev = json.loads(path.read_text())
            if prev.get("status") in ("ok", "skip"):
                print(f"[dryrun] cached {a} x {s} @ {mesh_name}")
                continue
        r = run_cell(a, s, multi_pod=mp, probes=not args.no_probes,
                     debug_mesh=args.debug_mesh,
                     microbatches=args.microbatches,
                     fsdp_gather=args.fsdp_gather, remat=args.remat,
                     explicit_sp=args.explicit_sp,
                     param_dtype=args.param_dtype,
                     capacity_factor=args.capacity_factor)
        _out_path(args.out, r).write_text(json.dumps(r, indent=2, default=str))
        if r["status"] == "error":
            failures += 1
    print(f"[dryrun] done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
