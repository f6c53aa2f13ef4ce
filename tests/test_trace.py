"""Trace record/replay: determinism, reconstruction, schema, synthesis.

The centerpiece is the record→replay→re-record fuzz: a seeded random
live run (mixed op programs reusing the test_sched_model generator shape,
driver-delivered semaphore wakes, attach/demote/resize control churn,
half the seeds under a ``DeadlineArbiter`` with mixed deadline traffic)
is recorded with op recording armed, reconstructed into a ``Workload``,
and replayed. Asserted bit-identical on the DECISION_CODES stream:

* replay vs replay under the same config (determinism);
* replay vs a replay of the *re-recorded* replay (reconstruction is a
  fixed point — nothing is lost or invented by the round trip).

Live-vs-replay equality is NOT asserted: sync blocks are re-encoded as
absolute-time ``sleep_until`` ops (a documented approximation), so the
replay reproduces the observed blocking behaviour, not the sync objects.

Also covered: the sleep-then-sync-block attribution corner in
``reconstruct``, exact ``events_processed`` accounting under batched
same-timestamp wakeups, schema round-trip/rejection, recorder
arm/disarm hygiene, synthesized workloads (arrival generators,
stragglers, node churn), the task-event CSV adapter, the A/B runner,
and the unified benchmark runner's discovery.
"""

import json
import random

import pytest

from repro.core import simtask as st
from repro.core.deadline import DeadlineArbiter
from repro.core.events import SimExecutor
from repro.core.policies import SchedCoop, SchedFair, SchedRR
from repro.core.task import Job
from repro.core.topology import Topology
from repro.trace import (
    ReplayConfig,
    Replayer,
    TraceRecorder,
    TraceSchemaError,
    Workload,
    diff_streams,
    load_trace,
    reconstruct,
)
from repro.trace import schema as trace_schema
from repro.trace import synth
from repro.trace.ab import run_ab, slo_ab_configs
from repro.trace.adapter import ALIBABA_COLUMNS, load_task_events

N_SEEDS = 10


# --------------------------------------------------------------------- #
# the recorded live fuzz driver
# --------------------------------------------------------------------- #
class _TaskModel:
    __slots__ = ("task", "sem", "blocks_total", "wakes_sent")

    def __init__(self, task, sem, blocks_total):
        self.task = task
        self.sem = sem
        self.blocks_total = blocks_total
        self.wakes_sent = 0

    @property
    def wakes_owed(self):
        return self.blocks_total - self.wakes_sent


def _spawn_random_task(sim, rng, job, *, deadline=None) -> _TaskModel:
    """The test_sched_model op-generator shape: a random program over
    compute/sleep/yield/checkpoint plus semaphore blocks the driver must
    wake (the sync ops the reconstruction re-encodes as sleep_until)."""
    sem = st.SimSemaphore(0)
    ops = []
    n_blocks = 0
    for _ in range(rng.randint(2, 6)):
        k = rng.random()
        if k < 0.35:
            ops.append(("compute", rng.uniform(3e-4, 4e-3)))
        elif k < 0.50:
            ops.append(("sleep", rng.uniform(3e-4, 4e-3)))
        elif k < 0.62:
            ops.append(("yield",))
        elif k < 0.76:
            ops.append(("checkpoint",))
        else:
            ops.append(("block",))
            n_blocks += 1

    def gen():
        for op in ops:
            if op[0] == "compute":
                yield st.compute(op[1])
            elif op[0] == "sleep":
                yield st.sleep(op[1])
            elif op[0] == "yield":
                yield st.yield_()
            elif op[0] == "checkpoint":
                yield st.checkpoint()
            else:
                yield st.sem_acquire(sem)

    return _TaskModel(sim.spawn(job, gen, deadline=deadline), sem, n_blocks)


def _deliver_wake(sim, tm: _TaskModel) -> None:
    tm.wakes_sent += 1
    if tm.sem.queue:
        sim.sched.unblock(tm.sem.queue.popleft())
    else:
        tm.sem.value += 1


def _record_fuzz(seed: int):
    """One seeded random live run, recorded; returns (records, the
    ReplayConfig matching the live executor)."""
    rng = random.Random(seed)
    use_deadline = seed % 2 == 0
    n_slots = rng.choice((2, 4, 8))
    arb = DeadlineArbiter(SchedCoop(quantum=0.01)) if use_deadline else None
    sim = SimExecutor(Topology(n_slots, 1), SchedCoop(quantum=0.01),
                      max_time=1e9, arbiter=arb)
    rec = TraceRecorder().attach_sim(sim, ops=True)

    jobs = [Job(f"trfz{seed}-{i}") for i in range(rng.randint(2, 3))]
    models = []

    def spawn(job):
        dl = None
        if use_deadline and rng.random() < 0.5:
            dl = sim.now() + rng.uniform(-0.005, 0.05)  # sometimes overdue
        models.append(_spawn_random_task(sim, rng, job, deadline=dl))

    for job in jobs:
        for _ in range(rng.randint(1, 3)):
            spawn(job)

    def advance(dt):
        sim.run(until=sim.now() + dt)

    for _ in range(rng.randint(20, 40)):
        op = rng.random()
        job = rng.choice(jobs)
        if op < 0.22:
            spawn(job)
        elif op < 0.45:
            owed = [m for m in models if m.wakes_owed > 0]
            if owed:
                _deliver_wake(sim, rng.choice(owed))
        elif op < 0.60:  # attach: promote or live policy swap
            pol = rng.choice((
                lambda: SchedCoop(quantum=0.005),
                lambda: SchedFair(slice_s=0.002),
                lambda: SchedRR(quantum=0.003),
            ))()
            sim.attach(job, policy=pol, share=rng.choice((1.0, 2.0)))
        elif op < 0.70:
            if job.lease is not None and job.lease.group.dedicated:
                sim.demote(job, share=rng.choice((None, 1.0)))
        elif op < 0.80:
            if job.lease is not None:
                job.lease.resize(rng.choice((0.5, 1.0, 3.0)))
        else:
            advance(rng.uniform(0.001, 0.01))
        advance(rng.uniform(0.0005, 0.004))

    for tm in models:
        while tm.wakes_owed > 0:
            _deliver_wake(sim, tm)
    sim.run()
    rec.detach_all()
    assert all(m.task.done for m in models)
    cfg = ReplayConfig(slots=n_slots, domains=1,
                       default_policy=("SCHED_COOP", 0.01),
                       arbiter="deadline" if use_deadline else "none")
    return rec.records(), cfg


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_record_replay_rerecord_bit_identical(seed):
    records, cfg = _record_fuzz(seed)
    wl = reconstruct(records)
    assert wl.tasks and wl.n_ops() > 0

    r1 = Replayer(wl, cfg).run(record=True)
    r2 = Replayer(wl, cfg).run(record=True)
    s1 = r1.normalized_records()
    d = diff_streams(s1, r2.normalized_records())
    assert d is None, f"seed {seed}: replay not deterministic: {d}"
    assert all(t.done for t in r1.tasks), f"seed {seed}: replay lost tasks"

    # fixed point: re-record the replay, reconstruct THAT, replay again —
    # the round trip must not lose or invent a single decision
    wl2 = reconstruct(s1)
    r3 = Replayer(wl2, cfg).run(record=True)
    d = diff_streams(s1, r3.normalized_records())
    assert d is None, f"seed {seed}: reconstruction not a fixed point: {d}"


def test_sync_block_after_sleep_not_misattributed():
    """A sem block landing right after a completed sleep must survive
    reconstruction as its own sleep_until (a sleep op explains at most
    one block)."""
    sim = SimExecutor(Topology(2, 1), SchedCoop(quantum=0.01), max_time=1e9)
    rec = TraceRecorder().attach_sim(sim, ops=True)
    sem = st.SimSemaphore(0)

    def gen():
        yield st.compute(0.001)
        yield st.sleep(0.002)
        yield st.sem_acquire(sem)     # blocks immediately after the sleep
        yield st.compute(0.001)

    task = sim.spawn(Job("corner"), gen)
    sim.run(until=0.01)               # sleep expired; now parked on sem
    assert sem.queue
    sim.sched.unblock(sem.queue.popleft())
    sim.run()
    rec.detach_all()
    assert task.done

    wl = reconstruct(rec.records())
    kinds = [op[0] for op in wl.tasks[0].ops]
    assert kinds == ["compute", "sleep", "sleep_until", "compute"]


# --------------------------------------------------------------------- #
# satellite: exact events_processed accounting under batched wakeups
# --------------------------------------------------------------------- #
def test_events_processed_exact_under_batched_wakeups():
    """Same-timestamp sleep expiries drain as one batch; the count must
    still equal the number of heap pops — identical to the staggered run
    where every wakeup is its own pop."""
    def run_one(stagger):
        sim = SimExecutor(Topology(8, 1), SchedCoop(quantum=0.01),
                          max_time=1e9)
        job = Job("wk")
        for i in range(8):
            dt = 0.01 + (i * 1e-6 if stagger else 0.0)

            def gen(dt=dt):
                yield st.compute(0.001)
                yield st.sleep(dt)
                yield st.compute(0.001)

            sim.spawn(job, gen)
        sim.run()
        return sim.events_processed

    batched, staggered = run_one(False), run_one(True)
    assert batched == staggered == 40  # 5 structural events per task


# --------------------------------------------------------------------- #
# recorder: arm/disarm hygiene, file streaming
# --------------------------------------------------------------------- #
def _tiny_run(recorder=None):
    sim = SimExecutor(Topology(2, 1), SchedCoop(quantum=0.01), max_time=1e9)
    if recorder is not None:
        recorder.attach_sim(sim, ops=True)
    job = Job("tiny")
    for _ in range(3):
        sim.spawn(job, lambda: iter((("compute", 0.001, 0.0),
                                     ("sleep", 0.002),
                                     ("yield",),
                                     ("compute", 0.001, 0.0))))
    sim.run()
    return sim


def test_recorder_arm_disarm_restores_clean_state():
    sim = SimExecutor(Topology(2, 1), SchedCoop(quantum=0.01), max_time=1e9)
    assert sim.sched._rec is None
    assert "_advance" not in sim.__dict__   # disarmed: class method, no shim
    rec = TraceRecorder().attach_sim(sim, ops=True)
    assert sim.sched._rec is rec.emit
    assert "_advance" in sim.__dict__       # armed: recording twin shadowed
    rec.detach_all()
    assert sim.sched._rec is None
    assert "_advance" not in sim.__dict__


def test_recorder_memory_vs_file_streams_identical(tmp_path):
    mem = TraceRecorder()
    _tiny_run(mem)
    mem.close()

    path = str(tmp_path / "run.jsonl")
    with TraceRecorder(path, meta={"who": "test"}) as filed:
        _tiny_run(filed)

    header, records = load_trace(path)
    assert header["kind"] == "decisions"
    assert header["meta"] == {"who": "test",
                              "clock_offset_ns": filed.meta["clock_offset_ns"]}
    # the sim is virtual-time deterministic, but tids/jids are process-
    # global — normalize both runs into a common (per-run-relative) space
    wl_mem, wl_file = reconstruct(mem.records()), reconstruct(records)
    assert len(wl_mem.tasks) == len(wl_file.tasks) == 3
    assert ([ts.ops for ts in wl_mem.tasks]
            == [ts.ops for ts in wl_file.tasks])


def test_disarmed_run_records_nothing():
    rec = TraceRecorder()
    _tiny_run(recorder=None)
    assert rec.records() == []


def test_recorder_clock_offset_lands_on_the_profiler_clock(tmp_path):
    """A ``time.monotonic`` stamp plus the header's ``clock_offset_ns``
    falls where the CPU profiler put a span taken at that stamp."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    off = TraceRecorder().meta["clock_offset_ns"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        before = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("offset.probe"):
            time.sleep(0.01)
        after = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    start = next(v for pl in pd.planes if pl.name == "Task Environment"
                 for k, v in pl.stats if k == "profile_start_time")
    ev = next(e for pl in pd.planes for ln in pl.lines for e in ln.events
              if e.name == "offset.probe")
    slack = 500_000  # ns
    assert before + off - slack <= start + ev.start_ns
    assert start + ev.end_ns <= after + off + slack
    assert ev.end_ns - ev.start_ns >= 10_000_000


# --------------------------------------------------------------------- #
# schema: round-trip + rejection
# --------------------------------------------------------------------- #
def test_workload_save_load_roundtrip(tmp_path):
    wl = synth.slo_workload(0.8, n_requests=40, seed=3)
    path = str(tmp_path / "wl.jsonl")
    wl.save(path)
    wl2 = Workload.load(path)
    assert wl2.jobs == wl.jobs
    assert wl2.tasks == wl.tasks
    assert wl2.control == wl.control


def test_decision_records_roundtrip_bit_exact():
    rec = TraceRecorder()
    _tiny_run(rec)
    rec.close()
    records = rec.records()
    assert records
    decoded = [trace_schema.decode_record(trace_schema.encode_record(r))
               for r in records]
    assert decoded == records  # floats round-trip exactly through JSON


def test_fast_json_encoder_matches_dumps():
    """The writer's direct formatter (``encode_record_json``) must decode
    to exactly what the ``encode_record`` + ``json.dumps`` path decodes
    to, across every payload shape — including the non-finite floats and
    structured payloads that take the fallback."""
    from repro.core.scheduler import (REC_DISPATCH, REC_DL_POST, REC_OP,
                                      REC_RESIZE, REC_SPAWN, REC_WAKE)
    rng = random.Random(7)
    recs = []
    for i in range(500):
        t = rng.random() * 100
        recs.append(rng.choice([
            (t, REC_DISPATCH, i, rng.randrange(8)),
            (t, REC_WAKE, i, None),
            (t, REC_RESIZE, i, rng.random()),
            (t, REC_SPAWN, i, (3, None, 1.5)),
            (t, REC_OP, i, ("compute", 0.25, None)),
        ]))
    recs.append((float("inf"), REC_DL_POST, 1, float("inf")))
    for r in recs:
        line = trace_schema.encode_record_json(r)
        via_dumps = json.dumps(trace_schema.encode_record(r),
                               separators=(",", ":"))
        assert json.loads(line) == json.loads(via_dumps), r
        assert trace_schema.decode_record(json.loads(line)) == r


def test_schema_rejections(tmp_path):
    def write(header):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps(header) + "\n")
        return str(p)

    good = trace_schema.make_header(trace_schema.KIND_DECISIONS)

    future = dict(good, version=trace_schema.SCHEMA_VERSION + 1)
    with pytest.raises(TraceSchemaError, match="version"):
        load_trace(write(future))

    alien = dict(good, schema="not-a-trace")
    with pytest.raises(TraceSchemaError, match="schema"):
        load_trace(write(alien))

    with pytest.raises(TraceSchemaError, match="kind"):
        load_trace(write(dict(good, kind="mystery")))

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(TraceSchemaError, match="empty"):
        load_trace(str(empty))

    with pytest.raises(TraceSchemaError, match="tag"):
        trace_schema.decode_record(["??", 0.0, 1, None])
    with pytest.raises(TraceSchemaError, match="op"):
        trace_schema.decode_op(["zz", 1.0])
    with pytest.raises(TraceSchemaError):
        Workload.from_lines([["X", 1, 2, 3]])


# --------------------------------------------------------------------- #
# synthesis: arrival generators, perturbations
# --------------------------------------------------------------------- #
def test_arrival_generators_deterministic_and_ordered():
    for gen in (synth.poisson_arrivals, synth.burst_arrivals,
                synth.diurnal_arrivals):
        a = gen(100.0, 300, seed=1)
        assert len(a) == 300
        assert all(y >= x for x, y in zip(a, a[1:]))
        assert a == gen(100.0, 300, seed=1)
        assert a != gen(100.0, 300, seed=2)


def test_stragglers_and_node_churn_replay():
    wl = synth.colocation_workload(n_requests=150, batch_tasks=2,
                                   batch_segments=60, seed=1)
    base_ops = wl.n_ops()
    straggled = synth.with_stragglers(wl, frac=0.2, factor=4.0, seed=2)
    assert straggled.n_ops() == base_ops  # stretched, not re-shaped

    def total_compute(w):
        return sum(op[1] for ts in w.tasks for op in ts.ops
                   if op[0] == "compute")

    assert total_compute(straggled) > total_compute(wl)

    churned = synth.with_node_churn(straggled, [(0.05, 4), (0.2, 8)])
    assert [c for c in churned.control if c[1] == "target"]
    r = Replayer(churned, ReplayConfig(
        slots=8, domains=2, default_policy=("SCHED_FAIR", 0.003))).run()
    assert all(t.done for t in r.tasks)
    assert r.events == r.sim.events_processed > 0


# --------------------------------------------------------------------- #
# adapter: task-event CSV -> workload
# --------------------------------------------------------------------- #
def test_adapter_google_style_rows():
    rows = [
        # [time, _, jid, tid, _, event] — GOOGLE_COLUMNS order
        ["0",       "-", "j1", "t1", "-", "0"],   # submit
        ["100000",  "-", "j1", "t1", "-", "1"],   # schedule
        ["600000",  "-", "j1", "t1", "-", "4"],   # finish: 0.5 s
        ["200000",  "-", "j1", "t2", "-", "0"],   # submit, never finishes
        ["300000",  "-", "j2", "t1", "-", "0"],
        ["300000",  "-", "j2", "t1", "-", "5"],   # killed before running
        ["garbage", "-", "j9", "t9", "-", "0"],   # malformed: skipped
    ]
    wl = load_task_events(rows, time_scale=1e-6, chunk_s=0.01,
                          default_duration=0.02)
    assert len(wl.tasks) == 2            # the killed task is dropped
    assert len(wl.jobs) == 1             # ...and with it its only job
    by_arrival = {round(ts.t, 6): ts for ts in wl.tasks}
    full = by_arrival[0.0]
    assert full.cost_hint == pytest.approx(0.5)
    assert len(full.ops) == 50           # 0.5 s chunked at 10 ms
    assert sum(op[1] for op in full.ops) == pytest.approx(0.5)
    defaulted = by_arrival[0.2]
    assert defaulted.cost_hint == pytest.approx(0.02)
    assert wl.meta["defaulted_durations"] == 1

    r = Replayer(wl, ReplayConfig(slots=2, domains=1)).run()
    assert all(t.done for t in r.tasks)


def test_adapter_alibaba_style_rows():
    rows = [
        # [tid, _, jid, _, event, time, end_time] — ALIBABA_COLUMNS order
        ["1", "-", "j1", "-", "ready",      "10", "12"],
        ["2", "-", "j1", "-", "ready",      "11", "14"],
        ["3", "-", "j2", "-", "terminated", "12", "13"],
    ]
    wl = load_task_events(rows, columns=ALIBABA_COLUMNS, chunk_s=0.5)
    assert len(wl.tasks) == 3
    assert [ts.t for ts in wl.tasks] == [0.0, 1.0, 2.0]  # shifted to t0
    assert wl.tasks[0].cost_hint == pytest.approx(2.0)
    assert len(wl.tasks[0].ops) == 4                     # 2 s / 0.5 s
    # the lone "terminated" row still yields a start (its `time` column)
    assert wl.tasks[2].cost_hint == pytest.approx(1.0)


def test_adapter_rejects_empty_and_unmapped():
    with pytest.raises(ValueError, match="empty"):
        load_task_events([])
    with pytest.raises(ValueError, match="columns"):
        load_task_events([["0", "1"]], columns={"time": 0})


# --------------------------------------------------------------------- #
# A/B runner
# --------------------------------------------------------------------- #
def test_slo_ab_smoke():
    wl = synth.slo_workload(0.8, n_requests=150, seed=0)
    cfg_deadline, cfg_share = slo_ab_configs()
    res = run_ab(wl, cfg_deadline, cfg_share,
                 name_a="deadline", name_b="share")
    a, b = res["a"], res["b"]
    # both sides finish every task (serve requests + batch segments)
    assert a.completed == b.completed == len(wl.tasks)
    assert a.deadline_tasks == b.deadline_tasks == 150
    assert len(a.latencies) == 150
    cmp = res["comparison"]
    assert set(cmp["miss_rate"]) == {"deadline", "share"}
    assert cmp["events"]["deadline"] > 0 and cmp["events"]["share"] > 0


# --------------------------------------------------------------------- #
# unified benchmark runner
# --------------------------------------------------------------------- #
def test_bench_runner_discovery():
    from benchmarks.run import _takes_argv, discover, run_csv

    names = discover()
    for expected in ("sched_ops", "trace_replay", "colocation",
                     "microservices", "faults", "multiprocess"):
        assert expected in names
    assert "common" not in names and "run" not in names

    import benchmarks.sched_ops
    import benchmarks.matmul_heatmap
    assert _takes_argv(benchmarks.sched_ops.main)        # forwards --smoke
    assert not _takes_argv(benchmarks.matmul_heatmap.main)
    assert callable(run_csv)                             # legacy path kept


def test_bench_runner_rejects_unknown_module(capsys):
    from benchmarks.run import run_all

    assert run_all(smoke=True, only=["does_not_exist"]) == 2
    assert "unknown benchmarks" in capsys.readouterr().err
