"""One run of one cell: deploy, warm up, drive the window, drain, check.

Everything a cell is made of is found by name: its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py`` and the configuration's plain reference in
``bench/reference/<reference>.py``. Adding a cell adds such files and
``BENCHMARK.json`` entries; nothing here names a cell.

The window drives ``InferenceServer.submit`` from one client thread
outside the scheduler. Each request carries an output list that stamps
``time.monotonic()`` on every append, so time to first token and the gaps
between tokens are taken from the client's side without touching the
program.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import queue
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import traffic as T
from bench.stats import pct

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: a request not answered this long after the window closes has failed
DRAIN_CAP_S = 60.0


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic mix) of a cell."""
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return w, json.loads((ROOT / conf["file"]).read_text()), \
        T.load_mix(w["traffic"])


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_cache() -> None:
    """JAX's persistent compile cache, for every program however quick to
    compile, so that a warm run compiles nothing."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def arch_config(conf: dict):
    """The program's ``ArchConfig`` with every size the file states."""
    from repro.configs.base import ArchConfig, get_arch

    names = {f.name for f in dataclasses.fields(ArchConfig)}
    return dataclasses.replace(
        get_arch(conf["arch"]),
        **{k: v for k, v in conf.items() if k in names})


class Stamped(list):
    """A request's output list: stamps each token as the server appends
    it, and reports the request's last token on ``finished``."""

    def __init__(self, want: int, finished: "queue.SimpleQueue", key):
        super().__init__()
        self.want = want
        self.times: list[float] = []
        self._finished = finished
        self._key = key

    def append(self, tok) -> None:
        self.times.append(time.monotonic())
        super().append(tok)
        if len(self) == self.want:
            self._finished.put(self._key)


@dataclasses.dataclass
class Sent:
    plan: T.Planned
    sent: float              # due time (open loop) or send time (closed)
    late: float              # how far behind its due time it went out
    output: Stamped
    req: object = None
    answered: bool = False   # finished when the drain ended


class HostBlas:
    """The co-located host job: USF tasks that each repeat one float32
    n x n x n matrix product and then sleep, as a CPU-bound co-runner."""

    def __init__(self, usf, spec: dict, nice: int):
        from repro.core.policies import SchedCoop
        from repro.core.task import Job

        self.usf = usf
        self.spec = spec
        self.job = Job("hostblas", nice=nice)
        usf.attach(self.job, policy=SchedCoop())
        n = spec["n"]
        rng = np.random.default_rng(0)
        self.a = rng.standard_normal((n, n), np.float32)
        self.b = rng.standard_normal((n, n), np.float32)
        self.flop = 2.0 * n ** 3
        self.done = [0] * spec["tasks"]
        self._stop = False
        self.tasks = []

    def _body(self, i: int) -> None:
        import jax

        out = np.empty_like(self.a)
        while not self._stop:
            with jax.profiler.TraceAnnotation("bench.hostblas"):
                np.matmul(self.a, self.b, out=out)
            self.done[i] += 1
            self.usf.sleep(self.spec["sleep_s"])

    def start(self) -> None:
        np.matmul(self.a, self.b)  # warm
        self.tasks = [self.usf.create(self._body, (i,), job=self.job,
                                      name=f"hostblas{i}")
                      for i in range(self.spec["tasks"])]

    def count(self) -> int:
        return sum(self.done)

    def stop(self) -> None:
        self._stop = True
        for t in self.tasks:
            self.usf.join(t, timeout=30.0)


class Deployment:
    """In-process serving: one ``UsfRuntime``, the mix's servers (each its
    own SCHED_COOP job, weights from the run's seed) and its co-job."""

    def __init__(self, arch, conf: dict, mix: dict, seed: int):
        from repro.core.policies import SchedCoop
        from repro.core.threads import UsfRuntime
        from repro.core.topology import Topology
        from repro.serve.engine import InferenceServer

        dep = mix["deployment"]
        if dep["mode"] != "in_process":
            raise ValueError(f"unknown deployment mode {dep['mode']!r}")
        self.conf = conf
        self.usf = UsfRuntime(Topology(dep["slots"], 1), SchedCoop())
        self.servers = []
        for i in range(dep["servers"]):
            s = InferenceServer(f"srv{i}", arch, self.usf,
                                max_batch=conf["max_batch"],
                                max_len=conf["max_len"], seed=i,
                                nice=dep["server_nice"])
            load_weights(s, conf, seed, i)
            self.servers.append(s)
        self.cojob = None
        if dep.get("cojob"):
            self.cojob = HostBlas(self.usf, dep["cojob"], dep["server_nice"])

    def start(self) -> None:
        for s in self.servers:
            s.start()

    def warm(self) -> None:
        """Run every program the window runs once: one short request per
        server, through the same entry and step shape."""
        from repro.serve.engine import Request

        reqs = [s.submit(Request(tokens=[1, 2, 3], max_new=2))
                for s in self.servers]
        for r in reqs:
            if not r.done.wait(timeout=1200.0):
                raise RuntimeError("warm-up request never finished")
        if self.cojob is not None:
            self.cojob.start()

    def counters(self) -> dict:
        c = {"wait_s": 0.0, "dispatches": 0}
        for s in self.servers:
            st = s._task.stats
            c["wait_s"] += st.wait_time
            c["dispatches"] += st.dispatches
        c["cojob_done"] = self.cojob.count() if self.cojob else 0
        return c

    def stop_cojob(self) -> None:
        if self.cojob is not None:
            self.cojob.stop()

    def shutdown(self) -> None:
        """Stop the servers and free everything they hold on the device."""
        for s in self.servers:
            s.stop()
        for s in self.servers:
            if s._task is not None:
                self.usf.join(s._task, timeout=120.0)
        self.usf.shutdown(timeout=30.0)
        for s in self.servers:
            s.params = None
            s._step = s._reference = None
        self.servers = []
        gc.collect()


def load_weights(server, conf: dict, seed: int, index: int) -> None:
    """Replace a server's weights by the benchmark's, made from the seed,
    after checking that they fill the same tree, shapes and dtypes."""
    import jax

    from bench import weights as W

    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), server.params)
    server.params = None
    gc.collect()
    params = W.served(conf, seed, index, conf["param_dtype"])
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise RuntimeError(f"benchmark weights do not fit the server's "
                           f"tree:\n{got}\nvs\n{want}")
    server.params = params


class Tracer:
    """Profiler over a sub-window, started and stopped from its own thread
    so the client never waits on it."""

    def __init__(self, start_at: float, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.start_at, self.seconds = start_at, seconds
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax

        time.sleep(max(0.0, self.start_at - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            time.sleep(self.seconds)
        jax.profiler.stop_trace()

    def file(self) -> Path:
        return next(Path(self.dir).rglob("*.xplane.pb"))

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def drive(dep: Deployment, mix: dict, reqs: list, seconds: float,
          tracer: Optional[Tracer] = None) -> dict:
    """Send the mix for ``seconds``, then drain. Returns the run record's
    client part: every request sent, the window, counter deltas."""
    from repro.serve.engine import Request

    import jax

    finished: queue.SimpleQueue = queue.SimpleQueue()
    sent: list[Sent] = []
    servers = dep.servers

    def send(p: T.Planned, key, at: float, due: float) -> None:
        out = Stamped(p.max_new, finished, key)
        with jax.profiler.TraceAnnotation("bench.submit"):
            r = Request(tokens=list(p.prompt), max_new=p.max_new,
                        arrival=due, output=out)
            servers[p.server].submit(r)
        sent.append(Sent(p, due, at - due, out, r))

    c0 = dep.counters()
    t0 = time.monotonic()
    t1 = t0 + seconds
    if tracer is not None:
        tracer.start_at = t0 + (seconds - tracer.seconds) / 2
        tracer.thread.start()
    loop = mix["arrivals"]["loop"]
    if loop == "open":
        for i, p in enumerate(reqs):
            due = t0 + p.due
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            send(p, i, time.monotonic(), due)
        time.sleep(max(0.0, t1 - time.monotonic()))
    else:
        nxt = 0
        for _ in range(mix["arrivals"]["clients"]):
            now = time.monotonic()
            send(reqs[nxt % len(reqs)], len(sent), now, now)
            nxt += 1
        while True:
            left = t1 - time.monotonic()
            if left <= 0:
                break
            try:
                finished.get(timeout=left)
            except queue.Empty:
                break
            now = time.monotonic()
            if now >= t1:
                break
            send(reqs[nxt % len(reqs)], len(sent), now, now)
            nxt += 1
    c1 = dep.counters()
    cap = t1 + DRAIN_CAP_S
    for s in sent:
        left = cap - time.monotonic()
        if left <= 0 or not s.req.done.wait(timeout=left):
            break
    drained = time.monotonic()
    for s in sent:
        s.answered = s.req.finished > 0
    if tracer is not None:
        tracer.thread.join()
    return {"t0": t0, "t1": t1, "drained": drained, "sent": sent,
            "counters": {k: c1[k] - c0[k] for k in c0}}


def choose_sample(sent: list, rng, tokens: int) -> list:
    """Finished requests to check: the longest, then a seeded draw, until
    ``tokens`` served tokens are in."""
    done = [s for s in sent if s.answered]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].output), len(done[i].plan.prompt)))
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    pick, n = [], 0
    for i in order:
        pick.append(done[i])
        n += len(done[i].output)
        if n >= tokens:
            break
    return pick


def check(conf: dict, seed: int, sent: list, sample_tokens: int, *,
          control: bool = False, rows: int = 8) -> dict:
    """Compare what the window served with the plain reference.

    ``max_logit_gap``: over a seeded sample of finished requests (the
    longest among them), the widest gap by which a served token's
    reference logit lies below the reference's best at that position.
    ``wrong_lengths``: requests the server marked finished with another
    number of tokens than asked. ``unanswered``: requests sent in the
    window and not finished when the drain ended. With ``control``, also
    the same gap for the tokens the float8 control puts first
    (``control_logit_gap``)."""
    ref = load_module("reference", conf["reference"])
    rng = np.random.default_rng(seed + 7)
    sample = choose_sample(sent, rng, sample_tokens)
    out = {"requests": len(sample),
           "tokens": sum(len(s.output) for s in sample),
           "max_logit_gap": 0.0, "control_logit_gap": 0.0,
           "wrong_lengths": sum(len(s.output) != s.plan.max_new
                                for s in sent if s.answered),
           "unanswered": sum(not s.answered for s in sent)}
    L = conf["max_len"]
    by_server: dict[int, list] = {}
    for s in sample:
        by_server.setdefault(s.plan.server, []).append(s)
    for server, group in sorted(by_server.items()):
        for b in range(0, len(group), rows):
            chunk = group[b:b + rows]
            seqs = np.zeros((rows, L), np.int32)
            for j, s in enumerate(chunk):
                seq = list(s.plan.prompt) + list(s.output)
                seqs[j, :len(seq)] = seq
            served, ctl = ref.gaps(conf, seed, server, seqs, control=control)
            for j, s in enumerate(chunk):
                P, S = len(s.plan.prompt), len(s.output)
                at = slice(P - 1, P + S - 1)
                out["max_logit_gap"] = max(out["max_logit_gap"],
                                           float(served[j, at].max()))
                if control:
                    out["control_logit_gap"] = max(
                        out["control_logit_gap"], float(ctl[j, at].max()))
    return out


def client_record(drv: dict, seconds: float) -> dict:
    """The client's view of the window, for the metric readers."""
    t0, t1 = drv["t0"], drv["t1"]
    sent = drv["sent"]
    attempted = [s for s in sent if t0 <= s.sent < t1]
    ttft, itl, late, tokens, failed = [], [], [], 0, 0
    for s in attempted:
        times = s.output.times
        late.append(s.late)
        if len(times) < s.plan.max_new:
            failed += 1
        # an unanswered request counts as waiting until the drain ended
        ttft.append((times[0] if times else drv["drained"]) - s.sent)
        itl.extend(np.diff(times).tolist())
    for s in sent:
        tokens += sum(t0 <= t < t1 for t in s.output.times)
    return {"seconds": seconds, "attempted": len(attempted),
            "failed": failed, "ttft_s": ttft, "itl_s": itl,
            "tokens_in_window": tokens, "late_s": late}


def metric_names(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: each metric that lists the cell, or lists no cells."""
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def limits_check(conf: dict, chk: dict) -> dict:
    """Each compared number beside its limit (``conf["limits"]``)."""
    return {k: {"value": chk[k], "limit": lim}
            for k, lim in conf["limits"].items()}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None,
             conf: Optional[dict] = None, mix: Optional[dict] = None,
             fault=None) -> tuple[dict, dict]:
    """One run. Returns (result line, check). ``conf``/``mix`` replace the
    cell's files (smaller sizes in tests); ``fault`` is called with the
    deployment before the window, to break the timed path in tests."""
    import jax

    from bench import peaks, work

    bench = bench or load_benchmark()
    _, conf0, mix0 = cell_spec(bench, cell)
    conf, mix = conf or conf0, mix or mix0
    enable_cache()
    arch = arch_config(conf)
    dev = jax.devices()[0]
    reqs = T.plan(mix, seed=seed, seconds=seconds, vocab=conf["vocab"],
                  n_servers=mix["deployment"]["servers"])
    dep = Deployment(arch, conf, mix, seed)
    dep.start()
    dep.warm()
    if fault is not None:
        fault(dep)
    setup_s = time.monotonic() - t_start
    tracer = Tracer(0.0, min(4.0, seconds / 2)) if trace else None
    try:
        drv = drive(dep, mix, reqs, seconds, tracer)
        dep.stop_cojob()
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        cojob = dep.cojob
        dep.shutdown()
        rec = {"client": client_record(drv, seconds),
               "counters": drv["counters"], "setup_s": setup_s,
               "cojob_flop": cojob.flop if cojob else None,
               "work": work.serve_step(conf), "trace": None}
        if trace:
            from bench import trace_reduce

            rec["trace"] = trace_reduce.reduce(tracer.file())
    finally:
        if tracer is not None:
            tracer.cleanup()
    rec["peak"] = peaks.peaks(dev.device_kind) if dev.platform == "tpu" \
        else None
    chk = check(conf, seed, drv["sent"], mix["check"]["sample_tokens"])
    checks = limits_check(conf, chk)
    metrics = {}
    for m in metric_names(bench, cell, trace):
        v = load_module("metrics", m["name"]).value(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": rec["client"]["attempted"],
           "failed": rec["client"]["failed"],
           "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in t["device_ops"]],
                            "idle_gaps": [list(x) for x in t["idle_gaps"]]}
    out["client"] = {"sent": len(drv["sent"]),
                     "late_p95_s": pct(rec["client"]["late_s"], 0.95),
                     "sample_requests": chk["requests"],
                     "sample_tokens": chk["tokens"]}
    out["check"] = checks
    return out, chk
