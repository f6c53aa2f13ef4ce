"""Smoke run of the serving path on a TPU: smoke output, not benchmark
numbers.

One chip (the default): one ``UsfRuntime`` co-locates two
``InferenceServer``s behind a ``Gateway``, each server its own SCHED_COOP
job, both at the published widths of ``smollm_360m`` with bf16 compute and
random weights from a seed (the paper's in-process co-location on one
device). A few fan-out requests with seeded prompts are served, and every
answer is checked against ``LM.forward`` run with the same weights
(``InferenceServer.reference_check``).

Four chips (``--chips 4``): ``MultiProcessGateway`` with four full-width
server processes, one per chip, coordinated by the node broker. The four
must answer alike (same seed, same weights), and each server checks its
own answers against the reference inside its process. This parent never
imports JAX.

Where JAX finds no TPU the script exits non-zero before any work. Any
failed phase raises, so the exit code is non-zero and no result is
printed. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Run from the repository root:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm_360m"
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = (16, 64)   # inclusive bounds, drawn per request
NEW_TOKENS = 16
MAX_BATCH = 8
MAX_LEN = 128
#: a served token is checked where the reference's top two logits are
#: more than this apart. Decode through the KV cache and the full forward
#: round differently in bf16: at full width their logits differ by at
#: most 0.074 at 16 layers (CPU, 48 positions), so 0.25 leaves room.
MARGIN = 0.25
TIMEOUT = 900.0         # seconds per fan-out, compilation included


class SmokeFailure(RuntimeError):
    pass


def make_prompts(vocab: int, n: int = N_REQUESTS, seed: int = SEED):
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(n)]


def _check_outputs(outputs: dict, who: str) -> None:
    for name, out in outputs.items():
        if len(out) != NEW_TOKENS:
            raise SmokeFailure(f"{who}: {name} answered {len(out)} tokens, "
                               f"expected {NEW_TOKENS}")


def _tally(checks: list) -> dict:
    bad = [c for c in checks if not c["ok"]]
    if bad:
        raise SmokeFailure(f"reference check failed: {bad}")
    checked = sum(c["checked"] for c in checks)
    if checked == 0:
        raise SmokeFailure("no served token cleared the margin: the "
                           "reference check would be vacuous")
    return {"positions": sum(c["positions"] for c in checks),
            "checked": checked, "mismatches": 0}


def serve_colocated(cfg, prompts, *, n_servers: int = 2) -> dict:
    """In-process co-location: ``n_servers`` servers (seeds 0..n-1) and a
    gateway on one runtime. Returns outputs, reference tallies, timings."""
    from repro.core.policies import SchedCoop
    from repro.core.threads import UsfRuntime
    from repro.core.topology import Topology
    from repro.serve.engine import Gateway, InferenceServer

    usf = UsfRuntime(Topology(2, 1), SchedCoop())
    try:
        t0 = time.monotonic()
        servers = [InferenceServer(f"srv{i}", cfg, usf, max_batch=MAX_BATCH,
                                   max_len=MAX_LEN, seed=SEED + i, nice=10)
                   for i in range(n_servers)]
        for s in servers:
            s.start()
        gw = Gateway(usf, servers)
        setup_s = time.monotonic() - t0
        recs: list = [None] * len(prompts)

        def client(i: int) -> None:
            recs[i] = gw.handle(prompts[i], max_new=NEW_TOKENS,
                                timeout=TIMEOUT)

        def run(ids) -> float:
            t = time.monotonic()
            tasks = [usf.create(functools.partial(client, i), job=gw.job,
                                name=f"client{i}") for i in ids]
            for task in tasks:
                # join re-raises a client's exception as UsfTaskError
                if not usf.join(task, timeout=TIMEOUT):
                    raise SmokeFailure("a fan-out request never returned")
            return time.monotonic() - t

        first_s = run([0])  # alone: its first decode step compiles
        rest_s = run(range(1, len(prompts)))
        gw.check_servers()
        for i, rec in enumerate(recs):
            _check_outputs(rec["outputs"], f"request {i}")
        t = time.monotonic()
        checks = [s.reference_check(prompts[i], recs[i]["outputs"][s.name],
                                    MARGIN)
                  for s in servers for i in range(len(prompts))]
        reference_s = time.monotonic() - t
        gw.check_servers()
        for s in servers:
            s.stop()
        return {"requests": len(prompts), "servers": n_servers,
                "served": [s.served for s in servers],
                "setup_s": setup_s, "first_fanout_s": first_s,
                "other_fanouts_s": rest_s, "reference_s": reference_s,
                "reference": _tally(checks),
                "outputs": [r["outputs"] for r in recs]}
    finally:
        usf.shutdown(timeout=30.0)


def serve_multiprocess(prompts, *, n_servers: int, smoke: bool = False
                       ) -> dict:
    """One server process per chip behind the broker-hosting gateway; the
    servers share a seed, so their answers must agree token for token."""
    from repro.serve.multiproc import MultiProcessGateway

    gw = MultiProcessGateway({f"srv{i}": ARCH for i in range(n_servers)},
                             smoke=smoke, max_batch=MAX_BATCH,
                             max_len=MAX_LEN)
    try:
        t0 = time.monotonic()
        gw.start(ready_timeout=TIMEOUT)
        setup_s = time.monotonic() - t0
        t = time.monotonic()
        recs = []
        for i, prompt in enumerate(prompts):
            rec = gw.handle(prompt, max_new=NEW_TOKENS, timeout=TIMEOUT)
            if rec["retried"]:
                raise SmokeFailure(f"request {i} was retried: {rec}")
            _check_outputs(rec["outputs"], f"request {i}")
            if len({tuple(o) for o in rec["outputs"].values()}) != 1:
                raise SmokeFailure(f"request {i}: servers disagree: "
                                   f"{rec['outputs']}")
            recs.append(rec)
        serve_s = time.monotonic() - t
        t = time.monotonic()
        checks = [s.reference_check(prompts[i], recs[i]["outputs"][s.name],
                                    MARGIN, timeout=TIMEOUT)
                  for s in gw.servers for i in range(len(prompts))]
        reference_s = time.monotonic() - t
        snap = gw.snapshot()["servers"]
        if any(v["restarts"] or not v["alive"] for v in snap.values()):
            raise SmokeFailure(f"a server process died: {snap}")
        return {"requests": len(prompts), "servers": n_servers,
                "devices": [s.device for s in gw.servers],
                "setup_s": setup_s, "fanouts_s": serve_s,
                "reference_s": reference_s, "reference": _tally(checks)}
    finally:
        gw.stop()


def _say(**kv) -> None:
    print("smoke:", json.dumps(kv), flush=True)


def one_chip() -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU ({devices}); nothing run")

    from repro.configs.base import get_arch
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_arch(ARCH)
    out = serve_colocated(cfg, make_prompts(cfg.vocab))
    out.pop("outputs")
    stats = devices[0].memory_stats() or {}
    _say(phase="colocated_1chip", arch=ARCH, compute_dtype=cfg.compute_dtype,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"), **out)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def four_chips() -> dict:
    from repro.configs.base import get_arch
    from repro.serve.multiproc import tpu_chip_count

    n = tpu_chip_count()
    if n < 4:
        sys.exit(f"chip_smoke: --chips 4 needs four TPU chips, found {n}; "
                 "nothing run")
    out = serve_multiprocess(make_prompts(get_arch(ARCH).vocab), n_servers=4)
    devices = out["devices"]
    if any(d["platform"] != "tpu" or d["count"] != 1 for d in devices):
        raise SmokeFailure(f"each server must hold one TPU chip: {devices}")
    if "jax" in sys.modules:
        raise SmokeFailure("the gateway process imported JAX")
    _say(phase="multiprocess_4chip", arch=ARCH, **out)
    return {"platform": "tpu", "kind": devices[0]["kind"],
            "count": len(devices)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: in-process co-location on one chip (default); "
                         "4: one server process per chip")
    args = ap.parse_args()
    device = one_chip() if args.chips == 1 else four_chips()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
