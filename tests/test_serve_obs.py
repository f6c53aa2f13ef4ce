"""Serving spans, counters and request stamps (``repro.trace.serve_obs``)
on a tiny model served from one ``UsfRuntime``."""

import glob
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_smoke
from repro.core.policies import SchedCoop
from repro.core.threads import UsfRuntime
from repro.core.topology import Topology
from repro.serve.engine import InferenceServer, Request
from repro.trace import serve_obs as obs

MAX_BATCH = 2
#: (prompt length, output tokens): more requests than rows, so some wait
SHAPES = [(3, 4), (5, 2), (9, 6), (4, 3), (6, 5)]


class Stamped(list):
    """An output list that stamps each token as it is appended, as a
    client that times its tokens does."""

    def __init__(self):
        super().__init__()
        self.times = []

    def append(self, tok):
        self.times.append(time.monotonic())
        super().append(tok)


def serve(shapes, *, max_batch=MAX_BATCH):
    """Serve one request per (prompt, output) shape, all submitted at once,
    then stop the server and join its worker: the counters are final."""
    usf = UsfRuntime(Topology(2, 1), SchedCoop())
    try:
        srv = InferenceServer("srv", get_smoke("smollm_360m"), usf,
                              max_batch=max_batch, max_len=32)
        srv.start()
        reqs = [srv.submit(Request(tokens=list(range(1, p + 1)), max_new=n,
                                   output=Stamped()))
                for p, n in shapes]
        for r in reqs:
            assert r.done.wait(timeout=120.0), "request never finished"
        srv.stop()
        assert usf.join(srv._task, timeout=60.0)
        return srv, reqs
    finally:
        usf.shutdown(timeout=5.0)


@pytest.fixture(scope="module")
def served():
    return serve(SHAPES)


def test_counters_hold_their_invariants(served):
    srv, reqs = served
    st = srv.stats()
    assert st["admitted"] == st["finished"] == srv.served == len(reqs)
    # each request occupies its row for every prompt position and every
    # output token but the last, which is never fed back
    assert st["rows"] == sum(p + n - 1 for p, n in SHAPES)
    assert st["steps"] <= st["rows"] <= st["steps"] * MAX_BATCH
    assert st["device_wait_s"] > 0.0
    phases = ("admit_s", "dispatch_s", "fetch_s", "bookkeep_s")
    assert st["host_s"] == pytest.approx(sum(st[k] for k in phases))
    assert all(st[k] > 0.0 for k in phases)
    for r, (p, n) in zip(reqs, SHAPES):
        assert len(r.output) == n
        assert 0.0 < r.arrival <= r.started <= r.first_token <= r.finished
    # two rows, five requests: the later ones waited in the queue
    assert max(r.started - r.arrival for r in reqs) > 0.0


def test_program_stamps_split_the_client_first_token_time(served):
    """Queue wait plus prefill, from the program's stamps, is the time to
    first token the client measures, within 1 ms."""
    _, reqs = served
    for r in reqs:
        queue_wait = r.started - r.arrival
        prefill = r.first_token - r.started
        client_ttft = r.output.times[0] - r.arrival
        assert abs(queue_wait + prefill - client_ttft) < 1e-3


def test_profiled_run_has_flat_phase_spans_and_parks(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(SHAPES[:3])
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    marked = set(obs.PHASES) | {obs.PARK}
    lines = [[(e.name, e.start_ns, e.end_ns) for e in ln.events
              if e.name in marked]
             for pl in pd.planes if pl.name.startswith("/host:")
             for ln in pl.lines]
    worker = [evs for evs in lines if any(n in obs.PHASES for n, _, _ in evs)]
    assert len(worker) == 1, "the phases of one server share one thread"
    evs = worker[0]
    assert {n for n, _, _ in evs} == marked
    for k, (name, a, b) in enumerate(evs):
        if name not in obs.PHASES:
            continue
        inner = [e for j, e in enumerate(evs)
                 if j != k and a <= e[1] and e[2] <= b]
        assert not inner, f"{name} [{a}, {b}] contains {inner}"


def test_compiles_count_new_shapes_only():
    usf = UsfRuntime(Topology(2, 1), SchedCoop())

    def answer(srv):
        r = srv.submit(Request(tokens=[1, 2, 3], max_new=3))
        assert r.done.wait(timeout=120.0), "request never finished"

    try:
        srv = InferenceServer("srv", get_smoke("smollm_360m"), usf,
                              max_batch=2, max_len=32)
        srv.start()
        answer(srv)
        n = obs.compiles()
        answer(srv)  # the same shapes again: nothing traces or compiles
        assert obs.compiles() == n
        wide = InferenceServer("wide", get_smoke("smollm_360m"), usf,
                               max_batch=3, max_len=32)
        wide.start()
        answer(wide)  # a step of another shape
        assert obs.compiles() > n
        assert obs.compiles_by_name().get("serve_step", 0) >= 2
        srv.stop()
        wide.stop()
    finally:
        usf.shutdown(timeout=5.0)

