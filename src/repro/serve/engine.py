"""Oversubscribed serving engine — the paper's §5.5 scenario, real JAX.

Each ``InferenceServer`` is a USF *job* with worker tasks that run
continuous-batching decode loops over a slot-based KV cache. The
request-queue get of an idle server is an intercepted USF blocking point,
and each step dispatch a preemption point, so SCHED_COOP multiplexes the
servers (and the gateway) over slots at *application* boundaries, never
preempting a decode burst mid-flight (the HBM-residency analogue of cache
affinity). The wait for each step's completion is not intercepted: the
worker keeps its slot while the device runs (the ``serve.device_wait``
span and counter, ``repro.trace.serve_obs``).

The gateway fans a request to several model servers and joins the
responses (the paper's agentic benchmark: LLaMA + GPT-2 + RoBERTa).

Two-level scheduling: the gateway and every server attach as their own
arbiter group (a dedicated SCHED_COOP instance each) with a slot ``share``
derived from ``nice`` unless given explicitly — the paper's
gateway-nice-0 / servers-nice-20 priority story expressed as slot leases,
with work-conserving borrowing when the gateway is idle.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policies import Policy, SchedCoop
from repro.core.scheduler import REC_REQ_DONE, REC_REQUEST
from repro.core.sync import CoopChannel, CoopEvent
from repro.core.task import Job
from repro.core.threads import UsfRuntime, UsfTaskError
from repro.launch.inputs import make_decode_inputs
from repro.models.base import init_tree
from repro.models.registry import build_model
from repro.runtime.sharding import Sharder
from repro.trace import serve_obs as obs
from repro.train.step import make_serve_step

_RID = itertools.count()


def _greedy_reference(model, sharder, params, tokens, positions):
    """Full-sequence forward (``LM.forward``, the plain reference of the
    decode path): per position, the argmax and the gap between the top two
    logits, both for row 0."""
    logits, _ = model.forward(params, {"tokens": tokens,
                                       "positions": positions}, sharder)
    lg = logits[0].astype(jnp.float32)
    top2 = jax.lax.top_k(lg, 2)[0]
    return jnp.argmax(lg, axis=-1), top2[:, 0] - top2[:, 1]


@dataclasses.dataclass
class Request:
    tokens: list[int]
    max_new: int = 8
    rid: int = dataclasses.field(default_factory=lambda: next(_RID))
    #: ``time.monotonic`` stamps: submitted (or due), given a cache row,
    #: first output token about to be appended, retired
    arrival: float = 0.0
    started: float = 0.0
    first_token: float = 0.0
    finished: float = 0.0
    #: absolute SLO deadline (``time.monotonic`` domain); None = best-effort
    deadline: Optional[float] = None
    output: list[int] = dataclasses.field(default_factory=list)
    done: Optional[CoopEvent] = None
    #: arbiter deadline token while posted (set by ``submit``)
    _dl_token: Optional[int] = None

    @property
    def latency(self) -> float:
        return self.finished - self.arrival

    @property
    def missed(self) -> bool:
        """True iff the request had an SLO and finished past it."""
        return (self.deadline is not None and self.finished > 0.0
                and self.finished > self.deadline)


class InferenceServer:
    """One model server (a Job): continuous batching over `max_batch` KV
    slots; requests are prefilled teacher-forced through the decode path
    and then generated greedily."""

    def __init__(self, name: str, cfg, usf: UsfRuntime, *,
                 max_batch: int = 2, max_len: int = 64, seed: int = 0,
                 nice: int = 0, share: Optional[float] = None,
                 policy: Optional[Policy] = None, auto_ckpt: bool = True):
        self.name = name
        self.cfg = cfg
        self.usf = usf
        self.job = Job(name, nice=nice, share=share)
        self._policy = policy
        self.lease = None  # set on start()
        self.max_batch = max_batch
        self.max_len = max_len
        self.queue = CoopChannel(usf)
        self.model = build_model(cfg)
        self.sharder = Sharder(None)
        self.params = init_tree(jax.random.PRNGKey(seed),
                                self.model.param_specs(), cfg.param_dtype)
        self._step = jax.jit(make_serve_step(self.model, self.sharder),
                             donate_argnums=(1,))
        # every decode dispatch is a preemption point: a broker revoke or
        # elastic shrink parks this worker within ~one engine step even
        # when the batch never drains (docs/PREEMPTION.md tier 3)
        self._auto_ckpt = auto_ckpt
        self._reference = jax.jit(
            functools.partial(_greedy_reference, self.model, self.sharder))
        self._task = None
        self._stop = False
        self._counters = obs.ServeCounters()
        obs.count_compiles()

    @property
    def served(self) -> int:
        """Requests retired so far."""
        return self._counters.finished

    def stats(self) -> dict:
        """A snapshot of the decode loop's counters
        (``repro.trace.serve_obs.ServeCounters``)."""
        return self._counters.as_dict()

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> Request:
        req.done = req.done or CoopEvent(self.usf)
        req.arrival = req.arrival or time.monotonic()
        if req.deadline is not None:
            # surface the SLO to the job-level arbiter: a DeadlineArbiter
            # folds it into its EDF/least-laxity grant order (and may fire
            # an urgent grant if laxity is already negative); the base
            # SlotArbiter has no post_deadline and the request degrades to
            # best-effort ordering.
            post = getattr(self.usf.sched.arbiter, "post_deadline", None)
            if post is not None:
                req._dl_token = post(self.job, req.deadline)
        rec = self.usf.sched._rec
        if rec is not None:
            rec((self.usf.sched.clock(), REC_REQUEST, req.rid,
                 (self.job.jid, req.deadline)))
        self.queue.put(req)
        return req

    def _retire(self, req: Request) -> None:
        rec = self.usf.sched._rec
        if rec is not None:
            rec((self.usf.sched.clock(), REC_REQ_DONE, req.rid, req.latency))
        if req._dl_token is not None:
            retire = getattr(self.usf.sched.arbiter, "retire_deadline", None)
            if retire is not None:
                retire(self.job, req._dl_token)
            req._dl_token = None

    def start(self) -> None:
        # the worker starts through the shared default group (a warm
        # server: its loop may already be building batches) and is then
        # re-homed LIVE into its own arbiter group — a dedicated intra-job
        # policy under a nice-weighted (or explicit) slot lease. attach
        # migrates the queued/running worker without draining it.
        self._task = self.usf.create(self._serve_loop, job=self.job,
                                     name=f"{self.name}-worker")
        if self.job.lease is None or not self.job.lease.group.dedicated:
            self.lease = self.usf.attach(
                self.job, policy=self._policy or SchedCoop(),
                share=self.job.share,
            )

    def set_policy(self, policy: Optional[Policy], *,
                   share: Optional[float] = None):
        """Live re-home the server without draining its decode loop (the
        rescale-driven policy change): a fresh dedicated intra-job policy
        swaps in place, or ``policy=None`` demotes the server into the
        shared default group (e.g. after its mesh collapsed and a
        dedicated slot claim no longer makes sense). Queued requests keep
        their place — the worker task migrates exactly once, mid-batch if
        it is running."""
        if policy is None:
            self.lease = self.usf.demote(self.job, share=share)
        else:
            self.lease = self.usf.attach(
                self.job, policy=policy,
                share=share if share is not None else self.job.share,
            )
        return self.lease

    def stop(self) -> None:
        self._stop = True
        self.queue.put(None)  # wake the worker

    def reference_check(self, prompt: list[int], served: list[int],
                        margin: float) -> dict:
        """Check served tokens against ``LM.forward`` with the same params,
        teacher-forced over ``prompt + served``.

        Decode (through the KV cache) and the full forward round
        differently, so a position counts only where the reference's top
        two logits are more than ``margin`` apart; there its argmax must
        equal the served token. The sequence is padded to ``max_len``
        (causal attention: the pad never reaches a checked position), so
        every request shares one compiled reference."""
        seq = list(prompt) + list(served)
        if not prompt or not served or len(seq) > self.max_len:
            raise ValueError(f"need a prompt and 1..max_len={self.max_len} "
                             f"tokens in all, got {len(prompt)}+{len(served)}")
        toks = np.zeros((1, self.max_len), np.int32)
        toks[0, :len(seq) - 1] = seq[:-1]
        pos = np.arange(self.max_len, dtype=np.int32)[None]
        if self.cfg.mrope_sections is not None:
            pos = np.broadcast_to(pos, (3, 1, self.max_len))
        best, gap = self._reference(self.params, jnp.asarray(toks),
                                    jnp.asarray(pos))
        at = slice(len(prompt) - 1, len(seq) - 1)
        best = np.asarray(best)[at]
        decided = np.asarray(gap)[at] > margin
        wrong = decided & (best != np.asarray(served))
        return {"positions": len(served), "checked": int(decided.sum()),
                "mismatches": int(wrong.sum()), "ok": not wrong.any()}

    # ------------------------------------------------------------------ #
    def _serve_loop(self) -> None:
        cfg = self.cfg
        B = self.max_batch
        c = self._counters
        clock = time.monotonic
        span = jax.profiler.TraceAnnotation
        cache, _, _ = make_decode_inputs(cfg, B, self.max_len,
                                         jax.random.PRNGKey(1))
        active: list[Optional[Request]] = [None] * B
        pos = np.zeros(B, np.int64)
        remaining = np.zeros(B, np.int64)
        pending_tokens: list[list[int]] = [[] for _ in range(B)]
        cur = np.zeros(B, np.int64)

        while not self._stop:
            # a fully idle server blocks off its slot (a ``usf.park``),
            # outside every phase span
            req = None
            if all(a is None for a in active):
                req = self.queue.get()
                if req is None:
                    continue  # the stop sentinel
            # admit requests into free slots (continuous batching)
            t0 = clock()
            with span(obs.ADMIT) as sp:
                admitted = []
                for i in range(B):
                    if active[i] is None:
                        if req is None:
                            req = self.queue.try_get()
                        if req is None:
                            if self._stop:
                                return
                            continue
                        req.started = clock()
                        active[i] = req
                        pos[i] = 0
                        remaining[i] = req.max_new
                        pending_tokens[i] = list(req.tokens)
                        cur[i] = pending_tokens[i].pop(0)
                        admitted.append(req.rid)
                        req = None
                rows = B - active.count(None)
                sp.set_metadata(rows=rows)
                if admitted:
                    sp.set_metadata(rid=" ".join(map(str, admitted)))
            t1 = clock()
            c.admit_s += t1 - t0
            c.admitted += len(admitted)

            # one engine step: each active slot advances one token
            if self._auto_ckpt:
                self.usf.checkpoint()  # may park: outside the dispatch span
            t2 = clock()
            with span(obs.DISPATCH, step=c.steps):
                toks = jnp.asarray(cur, jnp.int32)
                p = jnp.asarray(pos, jnp.int32)
                if cfg.mrope_sections is not None:
                    p = jnp.broadcast_to(p, (3, B))
                logits, cache = self._step(self.params, cache, toks, p)
            t3 = clock()
            with span(obs.DEVICE_WAIT):
                # not intercepted by USF: the worker keeps its slot while
                # the device runs
                logits.block_until_ready()
            t4 = clock()
            with span(obs.FETCH):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            t5 = clock()
            c.steps += 1
            c.rows += rows
            c.dispatch_s += t3 - t2
            c.device_wait_s += t4 - t3
            c.fetch_s += t5 - t4

            with span(obs.BOOKKEEP) as sp:
                done = 0
                for i in range(B):
                    req = active[i]
                    if req is None:
                        continue
                    pos[i] += 1
                    if pending_tokens[i]:
                        cur[i] = pending_tokens[i].pop(0)  # still prefilling
                        continue
                    if remaining[i] == req.max_new:
                        req.first_token = clock()
                    req.output.append(int(nxt[i]))
                    cur[i] = int(nxt[i])
                    remaining[i] -= 1
                    if remaining[i] <= 0 or pos[i] >= self.max_len - 1:
                        req.finished = clock()
                        c.finished += 1
                        done += 1
                        self._retire(req)
                        req.done.set()
                        active[i] = None
                if done:
                    sp.set_metadata(finished=done)
            c.bookkeep_s += clock() - t5


class Gateway:
    """Fans each request out to all servers; joins all responses (§5.5)."""

    def __init__(self, usf: UsfRuntime, servers: list[InferenceServer],
                 *, nice: int = 0, share: Optional[float] = None,
                 policy: Optional[Policy] = None):
        self.usf = usf
        self.servers = servers
        self.job = Job("gateway", nice=nice, share=share)
        # the gateway gets its own lease too (nice 0 -> heaviest share by
        # default, mirroring the paper's microservices priority setup)
        self.lease = usf.attach(self.job, policy=policy or SchedCoop(),
                                share=share)
        self.responses: list[dict] = []

    def check_servers(self) -> None:
        """A dead server worker would leave fanned-out requests pending
        forever: surface its task exception to the caller instead."""
        for s in self.servers:
            t = s._task
            if t is not None and getattr(t, "_exc", None) is not None:
                raise UsfTaskError(t, t._exc)

    def handle(self, tokens: list[int], max_new: int = 4,
               timeout: Optional[float] = None,
               slo: Optional[float] = None) -> dict:
        """Runs on the caller's USF task: submit to every server, wait all.

        Polls the response events so a crashed server worker raises
        ``UsfTaskError`` here rather than hanging the request; ``timeout``
        (wall seconds, whole fan-out) raises ``TimeoutError``. ``slo``
        (relative seconds) stamps every fanned request with an absolute
        deadline that a deadline-aware arbiter folds into its grant order;
        misses are recorded, never enforced."""
        t0 = time.monotonic()
        deadline = None if timeout is None else t0 + timeout
        dl = None if slo is None else t0 + slo
        reqs = []
        for s in self.servers:
            r = Request(tokens=list(tokens), max_new=max_new, arrival=t0,
                        deadline=dl)
            s.submit(r)
            reqs.append(r)
        for r in reqs:
            while True:
                poll = 0.5
                if deadline is not None:
                    poll = min(poll, max(deadline - time.monotonic(), 0.0))
                if r.done.wait(timeout=poll):
                    break
                self.check_servers()
                if deadline is not None and time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"gateway fan-out exceeded {timeout}s "
                        f"(request {r.rid})"
                    )
        rec = {
            "latency": time.monotonic() - t0,
            "per_server": {s.name: r.latency for s, r in zip(self.servers, reqs)},
            "outputs": {s.name: list(r.output)
                        for s, r in zip(self.servers, reqs)},
        }
        if slo is not None:
            rec["slo"] = slo
            rec["missed"] = any(r.missed for r in reqs)
        self.responses.append(rec)
        return rec
