"""Cross-process co-location under oversubscription — the paper's headline
multi-process claim, on real OS processes.

Two CPU-hungry worker *processes* (numpy compute phases meeting at a
per-process barrier each iteration — the nested-BLAS shape of §5.2/§5.3)
share one node:

* **free** — the Linux baseline: both processes run ``gating=False`` with
  unmodified busy-wait barriers, each sized to the whole node. 2x
  oversubscription: spinners burn cores (and the interpreter) while the
  sibling process fights for the same CPUs.
* **usf** — broker-coordinated: one ``NodeBroker`` (in the benchmark
  driver, the designated process) apportions the node across the worker
  processes; each worker's ``BrokerClient`` lands its grant on elastic
  slot parking (``UsfRuntime.set_slot_target``) and its threads meet at a
  cooperative barrier. Total running threads == node slots, no spin.

Scenarios:

* ``spin_colocate``: equal work, free vs broker-coordinated. Target:
  the co-location makespan (max across processes) improves **≥ 1.5x**.
* ``elastic_handoff``: unequal work, broker-coordinated vs *static*
  half-node caps (the bl-eq analogue at process level). When the small
  process finishes, the broker reclaims its lease and regrants the node
  to the survivor mid-run — work conservation a static partition cannot
  express.
* ``demand_feedback``: the idle/saturated phase shift. Two processes
  alternate busy bursts in antiphase (a baton of events serializes the
  turns); both stay alive and registered throughout. Bursts are
  *latency-bound*: each phase is a small matmul plus a blocking wait
  (the IO/RPC serving shape — the wait pins its slot, so granted width
  IS the achievable in-flight concurrency, independent of host core
  count). With static wants (``report_backlog=False`` — the
  pre-demand-feedback broker) each burst runs at half the node while
  the idle sibling pins its grant; with live backlog feedback the idle
  worker's effective want decays to zero within a few damped heartbeats
  and the saturated worker bursts at (nearly) full node width. Target:
  demand-aware beats static-want **≥ 1.3x** on makespan (asserted in
  full runs; smoke proves the machinery, including that demand-driven
  regrants actually fired).
* ``real_model``: the auto-checkpoint story on REAL jitted compute.
  (a) revoke-to-park: a node-width fleet of greedy-decode streams (a
  smoke-size transformer behind ``jax.jit``, zero USF calls in the loop
  body, instrumented only by ``autockpt.wrap_jit``) is elastically
  shrunk to half width; the surplus slots must park within a few
  dispatch intervals (p99 asserted in full runs), where the same
  streams UNWRAPPED cannot park before a stream's end — the
  previously-unbounded case. (b) colocate: N real model-server
  processes under sustained decode traffic, free-running (spin
  barriers, 2x oversubscription) vs NodeBroker-coordinated; same
  ≥ 1.5x makespan target as ``spin_colocate``, plus phase-latency
  p50/p99. Both modes run the *identical instrumented step* — the
  checkpoint no-op contract keeps the baseline unmodified.

Run:  PYTHONPATH=src python -m benchmarks.multiprocess [--smoke]
Writes BENCH_multiprocess.json (smoke: BENCH_multiprocess.smoke.json via
``make check``; the ratios are asserted only in full mode — CI smoke just
proves the machinery end-to-end).
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import sys
import time

from benchmarks.common import default_out, write_artifact

_CTX = mp.get_context("spawn")

N_PROCS = 2


def _node_slots() -> int:
    return max(2, min(os.cpu_count() or 2, 8))


def _colocate_worker(mode: str, broker_path, slots: int, threads: int,
                     phases: int, n: int, slot_cap, go, result_q,
                     name: str) -> None:
    """One worker process: ``threads`` compute/barrier tasks on its own
    runtime. ``mode``: free (unmanaged + spin barrier) | usf (gated +
    coop barrier, broker-coordinated when ``broker_path`` is set, or
    statically capped at ``slot_cap``)."""
    # our runtime provides the parallelism: a BLAS-internal thread pool
    # (spinning between calls) would add *uncoordinated* oversubscription
    # to every mode and drown the comparison in noise
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS",
                "MKL_NUM_THREADS"):
        os.environ.setdefault(var, "1")
    import numpy as np

    from repro.core.policies import SchedCoop
    from repro.core.sync import BusyWaitBarrier, CoopBarrier
    from repro.core.task import Job
    from repro.core.threads import UsfRuntime
    from repro.core.topology import Topology

    gating = mode == "usf"
    rt = UsfRuntime(Topology(slots, 1), SchedCoop(), gating=gating)
    client = None
    if gating and broker_path:
        from repro.ipc import BrokerClient

        client = BrokerClient(broker_path, name=name,
                              share=1.0).bind(rt).start()
        client.wait_grant(5.0)
    elif gating and slot_cap:
        rt.set_slot_target(slot_cap)  # static partition (no broker)
    bar = (CoopBarrier(rt, threads) if gating
           else BusyWaitBarrier(rt, threads, yield_every=None))
    job = Job(name)
    a = np.random.default_rng(0).standard_normal((n, n), dtype=np.float64)

    def body():
        x = a.copy()
        for _ in range(phases):
            x = x @ a                       # GIL-releasing compute burst
            x *= 1.0 / np.abs(x).max()
            bar.wait()                      # the per-phase team barrier

    go.wait()
    t0 = time.monotonic()
    tasks = [rt.create(body, job=job) for _ in range(threads)]
    for t in tasks:
        if not rt.join(t, timeout=600.0):
            result_q.put({"name": name, "error": "join timeout"})
            return
    makespan = time.monotonic() - t0
    granted = None if client is None else client.granted
    if client is not None:
        client.stop()  # deregister: survivors inherit this lease
    result_q.put({"name": name, "makespan": makespan,
                  "final_grant": granted})
    rt.shutdown(timeout=5.0)


def _run_colocation(mode: str, *, phases_per_proc, n: int,
                    coordinate: bool, slot_cap=None) -> dict:
    """Launch N_PROCS co-located workers, release them simultaneously,
    gather per-process makespans."""
    slots = _node_slots()
    broker = None
    path = None
    if coordinate:
        from repro.ipc import NodeBroker

        broker = NodeBroker(capacity=slots, heartbeat_timeout=2.0)
        path = broker.start()
    go = _CTX.Event()
    result_q = _CTX.Queue()
    procs = []
    for i, phases in enumerate(phases_per_proc):
        p = _CTX.Process(
            target=_colocate_worker,
            args=(mode, path, slots, slots, phases, n, slot_cap, go,
                  result_q, f"proc{i}"),
            daemon=True)
        p.start()
        procs.append(p)
    try:
        time.sleep(1.0)  # runtimes (and broker registrations) come up
        go.set()
        results = [result_q.get(timeout=600.0) for _ in procs]
    finally:
        for p in procs:
            p.join(30.0)
            if p.is_alive():
                p.terminate()
        if broker is not None:
            broker.stop()
    errs = [r for r in results if "error" in r]
    if errs:
        raise RuntimeError(f"worker failure: {errs}")
    by_name = {r["name"]: r for r in results}
    return {
        "mode": mode,
        "coordinated": coordinate,
        "node_slots": slots,
        "per_proc_makespan": {k: round(v["makespan"], 4)
                              for k, v in sorted(by_name.items())},
        "makespan": round(max(r["makespan"] for r in results), 4),
    }


def _phase_worker(broker_path, slots: int, threads: int, phases: int,
                  n: int, wait_s: float, batons, parity: int, go, result_q,
                  name: str, report_backlog: bool, hb: float) -> None:
    """One phase-shift worker: takes every other baton, bursts ``threads``
    latency-bound tasks (matmul + a blocking ``wait_s`` per phase — the
    blocking wait holds its slot, so burst time scales with
    ``threads / granted_width``). Between its turns the main thread
    blocks on a plain mp Event — the runtime is truly idle, so a
    demand-reporting heartbeat sees backlog 0 and the broker can drain
    this worker's lease to the busy sibling. ``report_backlog=False``
    replays the static-want (v1) broker contract as the A/B baseline."""
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS",
                "MKL_NUM_THREADS"):
        os.environ.setdefault(var, "1")
    import numpy as np

    from repro.core.policies import SchedCoop
    from repro.core.sync import CoopBarrier
    from repro.core.task import Job
    from repro.core.threads import UsfRuntime
    from repro.core.topology import Topology
    from repro.ipc import BrokerClient

    rt = UsfRuntime(Topology(slots, 1), SchedCoop())
    client = BrokerClient(broker_path, name=name, share=1.0,
                          heartbeat_interval=hb,
                          report_backlog=report_backlog).bind(rt).start()
    client.wait_grant(5.0)
    job = Job(name)
    a = np.random.default_rng(0).standard_normal((n, n), dtype=np.float64)
    go.wait()
    t0 = time.monotonic()
    for k in range(parity, len(batons) - 1, 2):
        batons[k].wait()                    # idle until it is our turn
        bar = CoopBarrier(rt, threads)

        def body():
            x = a.copy()
            for _ in range(phases):
                x = x @ a
                x *= 1.0 / np.abs(x).max()
                time.sleep(wait_s)          # blocking wait: pins the slot
                bar.wait()

        tasks = [rt.create(body, job=job) for _ in range(threads)]
        for t in tasks:
            if not rt.join(t, timeout=600.0):
                result_q.put({"name": name, "error": "join timeout"})
                return
        batons[k + 1].set()                 # sibling's turn
    makespan = time.monotonic() - t0
    result_q.put({"name": name, "makespan": makespan,
                  "final_grant": client.granted})
    client.stop()
    rt.shutdown(timeout=5.0)


def _run_phase_shift(*, report_backlog: bool, bursts_per_proc: int,
                     phases: int, n: int, wait_s: float) -> dict:
    """Antiphase busy/idle workers under one broker. The baton chain
    serializes the bursts, so the whole run is a sequence of
    (one saturated, one idle) intervals — the exact shape where live
    demand pays and static wants strand half the node."""
    slots = _node_slots()
    from repro.ipc import NodeBroker

    # fast demand knobs: the benchmark measures steady-burst throughput,
    # not damping latency, so keep the regrant reaction well under a
    # burst length (the same knobs are used for the static baseline,
    # where they are inert)
    broker = NodeBroker(capacity=slots, heartbeat_timeout=2.0,
                        demand_beats=2, min_regrant_interval=0.02)
    path = broker.start()
    n_bursts = bursts_per_proc * N_PROCS
    batons = [_CTX.Event() for _ in range(n_bursts + 1)]
    go = _CTX.Event()
    result_q = _CTX.Queue()
    procs = []
    for i in range(N_PROCS):
        p = _CTX.Process(
            target=_phase_worker,
            args=(path, slots, slots, phases, n, wait_s, batons, i, go,
                  result_q, f"proc{i}", report_backlog, 0.02),
            daemon=True)
        p.start()
        procs.append(p)
    try:
        time.sleep(1.0)  # runtimes and registrations come up
        go.set()
        batons[0].set()
        results = [result_q.get(timeout=600.0) for _ in procs]
        counters = {k: v for k, v in broker.snapshot().items()
                    if k in ("regrants", "demand_regrants", "grants_pushed",
                             "grants_suppressed")}
    finally:
        for p in procs:
            p.join(30.0)
            if p.is_alive():
                p.terminate()
        broker.stop()
    errs = [r for r in results if "error" in r]
    if errs:
        raise RuntimeError(f"worker failure: {errs}")
    by_name = {r["name"]: r for r in results}
    return {
        "mode": "demand" if report_backlog else "static_want",
        "node_slots": slots,
        "bursts_per_proc": bursts_per_proc,
        "per_proc_makespan": {k: round(v["makespan"], 4)
                              for k, v in sorted(by_name.items())},
        "makespan": round(max(r["makespan"] for r in results), 4),
        "broker_counters": counters,
    }


# --------------------------------------------------------------------------- #
# real_model: auto-checkpointed JAX decode under revocation + co-location
# --------------------------------------------------------------------------- #
def _pin_host_parallelism() -> None:
    """Single-threaded BLAS *and* XLA CPU backend (must run before the
    first ``import jax``): the USF runtime's streams are the only source
    of parallelism, so a slot grant maps 1:1 onto a busy core and the
    free-running baseline oversubscribes exactly N_PROCS x."""
    # these workers measure host scheduling and are regression guards,
    # not device numbers: keep them on the CPU, so that several of them
    # (and `make check` on a TPU host) never contend for a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS",
                "MKL_NUM_THREADS"):
        os.environ.setdefault(var, "1")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
          " intra_op_parallelism_threads=1"
    ).strip()


def _real_model_setup(slots: int, *, gating: bool = True):
    """Shared worker prologue: smoke-size real model + ONE jitted decode
    step (compiled once per process, shared by every stream)."""
    import jax

    from repro.configs.base import get_smoke
    from repro.core.policies import SchedCoop
    from repro.core.threads import UsfRuntime
    from repro.core.topology import Topology
    from repro.models.base import init_tree
    from repro.models.registry import build_model
    from repro.runtime.sharding import Sharder
    from repro.train.step import make_serve_step

    cfg = get_smoke("smollm_360m")
    model = build_model(cfg)
    sharder = Sharder(None)
    params = init_tree(jax.random.PRNGKey(0), model.param_specs(),
                       cfg.param_dtype)
    step = jax.jit(make_serve_step(model, sharder))
    rt = UsfRuntime(Topology(slots, 1), SchedCoop(), gating=gating)
    return cfg, params, step, rt


def _real_revoke_worker(slots: int, revokes: int, ctrl_steps: int,
                        result_q) -> None:
    """Revoke-to-park latency against REAL jitted decode streams.

    ``slots`` streams run uninstrumented greedy-decode loops — each
    iteration is one jitted dispatch + ``block_until_ready`` with no
    USF call anywhere in the body — behind ``autockpt.wrap_jit``. Each
    revoke cycle shrinks the runtime to half width and times
    ``set_slot_target`` -> every surplus slot parked; the bound under
    test is a few *dispatch intervals*, the paper's blocking-point
    granularity argument applied to opaque compute. A control round runs
    the same streams UNWRAPPED: the revoke then lands only at a stream's
    end — the previously-unbounded case (docs/PREEMPTION.md tier 3)."""
    _pin_host_parallelism()
    import threading

    import jax
    import jax.numpy as jnp

    from repro.core.autockpt import wrap_jit
    from repro.core.task import Job
    from repro.launch.inputs import make_decode_inputs

    try:
        cfg, params, step, rt = _real_model_setup(slots)
        wstep = wrap_jit(step, runtime=rt)
        max_len = 32
        target = max(1, slots // 2)
        surplus = slots - target
        stop = threading.Event()
        measuring = threading.Event()  # full-width steady-state window only
        counts = [0] * slots
        intervals: list = []  # pre-revoke steady-state dispatch intervals

        def make_body(i, fn, n_steps=None):
            def body():
                cache, tok, p = make_decode_inputs(
                    cfg, 1, max_len, jax.random.PRNGKey(i))
                last = time.monotonic()
                k = 0
                while not stop.is_set() and (n_steps is None or k < n_steps):
                    logits, cache = fn(params, cache, tok, p)
                    logits.block_until_ready()  # the device wait
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    p = (p + 1) % (max_len - 1)
                    now = time.monotonic()
                    if measuring.is_set():
                        intervals.append(now - last)
                    last = now
                    counts[i] += 1
                    k += 1

            return body

        job = Job("real-decode")
        tasks = [rt.create(make_body(i, wstep), job=job)
                 for i in range(slots)]
        deadline = time.monotonic() + 300.0
        while min(counts) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert min(counts) >= 3, "streams never warmed up (compile stuck?)"
        measuring.set()
        time.sleep(0.25)  # steady-state interval sample at full width
        measuring.clear()

        park_lats = []
        steps_during = []
        for _ in range(revokes):
            before = sum(counts)
            t0 = time.monotonic()
            rt.set_slot_target(target)
            while len(rt.sched.parked_slot_ids()) < surplus \
                    and time.monotonic() < deadline:
                time.sleep(0.0002)
            lat = time.monotonic() - t0
            assert len(rt.sched.parked_slot_ids()) >= surplus, \
                "revoke never parked the surplus slots"
            park_lats.append(lat)
            steps_during.append(sum(counts) - before)
            rt.set_slot_target(None)   # regrant: parked slots resume
            time.sleep(0.05)
        stop.set()
        for t in tasks:
            assert rt.join(t, timeout=60.0)

        # control: identical streams, UNWRAPPED — no scheduling point
        # until a stream finishes, so the revoke waits for a task END
        stop.clear()
        ctrl_counts_before = sum(counts)
        ctrl = [rt.create(make_body(i, step, n_steps=ctrl_steps), job=job)
                for i in range(slots)]
        while sum(counts) - ctrl_counts_before < slots \
                and time.monotonic() < deadline:
            time.sleep(0.002)  # every stream mid-flight
        t0 = time.monotonic()
        rt.set_slot_target(target)
        while not rt.sched.parked_slot_ids() \
                and time.monotonic() < deadline:
            time.sleep(0.0005)
        control_lat = time.monotonic() - t0
        control_parked = bool(rt.sched.parked_slot_ids())
        rt.set_slot_target(None)
        for t in ctrl:
            assert rt.join(t, timeout=120.0)
        rt.shutdown(timeout=10.0)

        xs = sorted(park_lats)

        def pct(p: float) -> float:
            return xs[min(len(xs) - 1, int(round(p * (len(xs) - 1))))]

        step_mean = (sum(intervals) / len(intervals)) if intervals else 0.0
        result_q.put({
            "streams": slots, "slot_target": target,
            "revoke_cycles": len(xs),
            "park_p50_s": pct(0.50), "park_p99_s": pct(0.99),
            "park_max_s": xs[-1],
            "step_mean_s": step_mean,
            "steps_during_park_mean": sum(steps_during) / len(steps_during),
            "control_park_s": control_lat,
            "control_parked": control_parked,
            "control_steps": ctrl_steps,
        })
    except BaseException as e:  # noqa: BLE001 — surface to the driver
        result_q.put({"error": f"{type(e).__name__}: {e}"})


def _real_colocate_worker(mode: str, broker_path, slots: int, phases: int,
                          go, result_q, name: str) -> None:
    """One model-server process for the co-location A/B: ``slots``
    auto-wrapped decode streams meeting at a per-phase barrier.

    The step wrapper is UNCONDITIONAL in both modes — the satellite
    no-op contract means the free-running baseline executes the exact
    same instrumented code (checkpoints vanish without a gated task), so
    the A/B isolates coordination, not instrumentation."""
    _pin_host_parallelism()
    import jax
    import jax.numpy as jnp

    from repro.core.autockpt import wrap_jit
    from repro.core.sync import BusyWaitBarrier, CoopBarrier
    from repro.core.task import Job
    from repro.launch.inputs import make_decode_inputs

    try:
        gating = mode == "usf"
        cfg, params, step, rt = _real_model_setup(slots, gating=gating)
        wstep = wrap_jit(step, runtime=rt)
        client = None
        if gating and broker_path:
            from repro.ipc import BrokerClient

            client = BrokerClient(broker_path, name=name,
                                  share=1.0).bind(rt).start()
            client.wait_grant(5.0)
        bar = (CoopBarrier(rt, slots) if gating
               else BusyWaitBarrier(rt, slots, yield_every=None))
        max_len = 32
        phase_lats: list = []  # stream 0's inter-barrier times

        def make_body(i):
            def body():
                cache, tok, p = make_decode_inputs(
                    cfg, 1, max_len, jax.random.PRNGKey(i))
                last = time.monotonic()
                for _ in range(phases):
                    logits, cache, _ = wstep(params, cache, tok, p)
                    logits.block_until_ready()
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    p = (p + 1) % (max_len - 1)
                    bar.wait()
                    if i == 0:
                        now = time.monotonic()
                        phase_lats.append(now - last)
                        last = now

            return body

        # compile before the gun so both modes time steady-state decode
        warm_cache, warm_tok, warm_p = make_decode_inputs(
            cfg, 1, max_len, jax.random.PRNGKey(99))
        step(params, warm_cache, warm_tok, warm_p)[0].block_until_ready()

        go.wait()
        t0 = time.monotonic()
        job = Job(name)
        tasks = [rt.create(make_body(i), job=job) for i in range(slots)]
        for t in tasks:
            if not rt.join(t, timeout=600.0):
                result_q.put({"name": name, "error": "join timeout"})
                return
        makespan = time.monotonic() - t0
        if client is not None:
            client.stop()
        # drop the first phase (it absorbs dispatch-path warmup jitter)
        result_q.put({"name": name, "makespan": makespan,
                      "phase_lats": phase_lats[1:]})
        rt.shutdown(timeout=10.0)
    except BaseException as e:  # noqa: BLE001 — surface to the driver
        result_q.put({"name": name, "error": f"{type(e).__name__}: {e}"})


def _run_real_colocation(mode: str, *, phases: int) -> dict:
    """N_PROCS real-model servers co-located on the node, free vs
    broker-coordinated — the spin_colocate A/B with jitted decode."""
    from benchmarks.common import summarize_latencies

    slots = _node_slots()
    broker = None
    path = None
    if mode == "usf":
        from repro.ipc import NodeBroker

        broker = NodeBroker(capacity=slots, heartbeat_timeout=2.0)
        path = broker.start()
    go = _CTX.Event()
    result_q = _CTX.Queue()
    procs = []
    for i in range(N_PROCS):
        p = _CTX.Process(
            target=_real_colocate_worker,
            args=(mode, path, slots, phases, go, result_q, f"proc{i}"),
            daemon=True)
        p.start()
        procs.append(p)
    try:
        time.sleep(1.0)  # runtimes, model compile, broker registrations
        go.set()
        results = [result_q.get(timeout=900.0) for _ in procs]
    finally:
        for p in procs:
            p.join(30.0)
            if p.is_alive():
                p.terminate()
        if broker is not None:
            broker.stop()
    errs = [r for r in results if "error" in r]
    if errs:
        raise RuntimeError(f"real-model worker failure: {errs}")
    by_name = {r["name"]: r for r in results}
    lats = [x for r in results for x in r["phase_lats"]]
    out = {
        "mode": mode,
        "node_slots": slots,
        "phases": phases,
        "per_proc_makespan": {k: round(v["makespan"], 4)
                              for k, v in sorted(by_name.items())},
        "makespan": round(max(r["makespan"] for r in results), 4),
    }
    out.update(summarize_latencies(lats, prefix="phase_", round_to=6))
    return out


def _run_real_model(*, smoke: bool) -> dict:
    """The real_model scenario: (a) revoke-to-park latency on live jitted
    decode streams, (b) coordinated-vs-free co-location makespan/p99."""
    slots = _node_slots()
    revokes = 5 if smoke else 20
    ctrl_steps = 60 if smoke else 200
    result_q = _CTX.Queue()
    p = _CTX.Process(target=_real_revoke_worker,
                     args=(slots, revokes, ctrl_steps, result_q),
                     daemon=True)
    p.start()
    try:
        revoke = result_q.get(timeout=900.0)
    finally:
        p.join(60.0)
        if p.is_alive():
            p.terminate()
    if "error" in revoke:
        raise RuntimeError(f"real-model revoke worker: {revoke['error']}")

    phases = 40 if smoke else 300
    free = _run_real_colocation("free", phases=phases)
    usf = _run_real_colocation("usf", phases=phases)
    speedup = free["makespan"] / usf["makespan"]
    return {
        "revoke_to_park": revoke,
        "colocate": {
            "free": free,
            "usf": usf,
            "speedup": round(speedup, 3),
            "target": 1.5,
            "meets_target": speedup >= 1.5,
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="output JSON (default BENCH_multiprocess.json, "
                         "or BENCH_multiprocess.smoke.json with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny work: proves the machinery, skips the "
                         "ratio assertion (CI hosts are noisy)")
    args = ap.parse_args(argv)
    phases = 12 if args.smoke else 80
    n = 96 if args.smoke else 128

    # -- scenario 1: equal co-location, free vs broker-coordinated ------- #
    free = _run_colocation("free", phases_per_proc=[phases] * N_PROCS,
                           n=n, coordinate=False)
    usf = _run_colocation("usf", phases_per_proc=[phases] * N_PROCS,
                          n=n, coordinate=True)
    speedup = free["makespan"] / usf["makespan"]
    print(f"spin_colocate ({N_PROCS} procs x {free['node_slots']} threads, "
          f"{phases} phases):")
    print(f"  free-running (oversubscribed busy-wait): "
          f"{free['makespan']:.3f}s  {free['per_proc_makespan']}")
    print(f"  broker-coordinated:                      "
          f"{usf['makespan']:.3f}s  {usf['per_proc_makespan']}")
    print(f"  speedup: {speedup:.2f}x (target >= 1.5x)")

    # -- scenario 2: unequal work — elastic handoff vs static split ------ #
    slots = _node_slots()
    # the small process exits early; the survivor's long tail is where the
    # reclaimed lease pays (the tail must dominate its pre-handoff phase,
    # and each phase must be coarse enough that extra width beats the
    # cross-thread barrier cost — hence the bigger matmul)
    uneven = [max(2, phases // 16), phases]
    n_handoff = 192 if args.smoke else 256
    static = _run_colocation("usf", phases_per_proc=uneven, n=n_handoff,
                             coordinate=False, slot_cap=max(1, slots // 2))
    elastic = _run_colocation("usf", phases_per_proc=uneven, n=n_handoff,
                              coordinate=True)
    handoff = static["makespan"] / elastic["makespan"]
    print(f"elastic_handoff (uneven work {uneven}):")
    print(f"  static half-node caps: {static['makespan']:.3f}s  "
          f"{static['per_proc_makespan']}")
    print(f"  broker (lease reclaimed at exit): {elastic['makespan']:.3f}s  "
          f"{elastic['per_proc_makespan']}")
    print(f"  work-conservation gain: {handoff:.2f}x")

    # -- scenario 3: idle/saturated phase shift — live demand vs static -- #
    # each burst must dwarf the demand-damping latency (a few heartbeats
    # + min-regrant interval, ~0.1s with the bench knobs) or the regrant
    # reaction time eats the concurrency gain — hence the coarse full-run
    # burst (~1s at the static half-node width)
    bursts = 1 if args.smoke else 2
    ps_phases = 10 if args.smoke else 60
    ps_wait = 0.005 if args.smoke else 0.008
    static_ps = _run_phase_shift(report_backlog=False,
                                 bursts_per_proc=bursts,
                                 phases=ps_phases, n=n, wait_s=ps_wait)
    demand_ps = _run_phase_shift(report_backlog=True,
                                 bursts_per_proc=bursts,
                                 phases=ps_phases, n=n, wait_s=ps_wait)
    feedback = static_ps["makespan"] / demand_ps["makespan"]
    print(f"demand_feedback (antiphase bursts, {bursts} per proc, "
          f"{ps_phases} phases):")
    print(f"  static wants (idle sibling pins half): "
          f"{static_ps['makespan']:.3f}s  {static_ps['per_proc_makespan']}")
    print(f"  live backlog feedback:                 "
          f"{demand_ps['makespan']:.3f}s  {demand_ps['per_proc_makespan']}  "
          f"counters={demand_ps['broker_counters']}")
    print(f"  demand-feedback gain: {feedback:.2f}x (target >= 1.3x)")
    # machinery check, valid even in smoke: the demand run must have
    # actually moved leases on backlog feedback, and the static run must
    # not have (its clients beat without the field)
    if demand_ps["broker_counters"]["demand_regrants"] < 1:
        print("FAIL: demand run triggered no demand-driven regrants",
              file=sys.stderr)
        return 1
    if static_ps["broker_counters"]["demand_regrants"] != 0:
        print("FAIL: static-want run saw demand-driven regrants",
              file=sys.stderr)
        return 1

    # -- scenario 4: real-model decode — bounded revocation + co-location #
    real = _run_real_model(smoke=args.smoke)
    rev = real["revoke_to_park"]
    col = real["colocate"]
    print(f"real_model (jitted decode, {rev['streams']} streams, "
          f"{rev['revoke_cycles']} revoke cycles):")
    print(f"  dispatch interval (steady state): "
          f"{rev['step_mean_s'] * 1e3:.2f}ms")
    print(f"  revoke-to-park: p50 {rev['park_p50_s'] * 1e3:.2f}ms "
          f"p99 {rev['park_p99_s'] * 1e3:.2f}ms "
          f"(~{rev['steps_during_park_mean']:.1f} node-wide dispatches)")
    print(f"  unwrapped control: parked after {rev['control_park_s']:.3f}s "
          f"(only at a stream's END, {rev['control_steps']} steps)")
    print(f"  colocate free: {col['free']['makespan']:.3f}s "
          f"(phase p99 {col['free']['phase_p99'] * 1e3:.1f}ms)  "
          f"usf: {col['usf']['makespan']:.3f}s "
          f"(phase p99 {col['usf']['phase_p99'] * 1e3:.1f}ms)")
    print(f"  speedup: {col['speedup']:.2f}x (target >= 1.5x)")
    # machinery checks, valid in smoke too: every revoke parked, and the
    # wrapped streams parked in bounded time while the unwrapped control
    # could not park before a stream boundary
    if not rev["control_parked"]:
        print("FAIL: real_model control round never parked", file=sys.stderr)
        return 1
    if rev["park_p99_s"] >= rev["control_park_s"]:
        print("FAIL: wrapped revoke-to-park not faster than the "
              "stream-boundary control", file=sys.stderr)
        return 1

    payload = {
        "bench": "multiprocess",
        "smoke": args.smoke,
        "n_procs": N_PROCS,
        "node_slots": slots,
        "phases": phases,
        "matmul_n": n,
        "scenarios": {
            "spin_colocate": {
                "free": free,
                "usf": usf,
                "speedup": round(speedup, 3),
                "target": 1.5,
                "meets_target": speedup >= 1.5,
            },
            "elastic_handoff": {
                "static": static,
                "elastic": elastic,
                "gain": round(handoff, 3),
            },
            "demand_feedback": {
                "static": static_ps,
                "demand": demand_ps,
                "gain": round(feedback, 3),
                "target": 1.3,
                "meets_target": feedback >= 1.3,
            },
            "real_model": real,
        },
    }
    write_artifact(default_out("multiprocess", args.smoke, args.out), payload)
    if not args.smoke and speedup < 1.5:
        print(f"FAIL: broker-coordinated speedup {speedup:.2f}x < 1.5x",
              file=sys.stderr)
        return 1
    if not args.smoke and feedback < 1.3:
        print(f"FAIL: demand-feedback gain {feedback:.2f}x < 1.3x",
              file=sys.stderr)
        return 1
    if not args.smoke:
        # bounded-latency claim: surplus slots park within a few dispatch
        # intervals (generous floor absorbs scheduler/poll granularity)
        bound = max(4.0 * rev["step_mean_s"], 0.025)
        if rev["park_p99_s"] > bound:
            print(f"FAIL: revoke-to-park p99 {rev['park_p99_s'] * 1e3:.1f}ms "
                  f"> bound {bound * 1e3:.1f}ms "
                  f"(~4 dispatch intervals)", file=sys.stderr)
            return 1
        if col["speedup"] < 1.5:
            print(f"FAIL: real-model coordinated speedup "
                  f"{col['speedup']:.2f}x < 1.5x", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
