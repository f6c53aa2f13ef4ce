"""Spans and counters of the serving path, on the device trace's clock.

Spans are ``jax.profiler.TraceAnnotation``: while a profile is active they
land in the trace's own host planes, on its clock, beside the device's
operations; while none is, each costs well under a microsecond. Every
pass of ``InferenceServer._serve_loop`` writes one flat span per phase.
No phase span encloses another or a park, so a reduction that names each
device-idle stretch after the host event overlapping it most names the
phase itself:

- ``serve.admit``: filling free cache rows from the request queue
  (attributes ``rows``, and ``rid`` of each request admitted);
- ``serve.dispatch``: the step's inputs and the host side of the step
  call (``step``);
- ``serve.device_wait``: ``block_until_ready`` on the step's logits. The
  device runs while the worker keeps its USF slot: the scheduler does not
  intercept this wait;
- ``serve.fetch``: the argmax and its copy to the host. The argmax is a
  device program of its own: where servers share a chip it queues behind
  another server's step;
- ``serve.bookkeep``: advancing rows, appending tokens, retiring requests
  (``finished``).

``usf.park`` (``UsfRuntime._park``) covers each wait of a USF task off its
slot, blocked or ready: a server idle on its queue, a preemption at the
dispatch boundary, a co-located job's sleep.

Counters are always on. ``ServeCounters`` are one server's
(``InferenceServer.stats()``); ``compiles()`` is the process's.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import Counter

import jax

from repro.core.threads import PARK_SPAN as PARK

ADMIT = "serve.admit"
DISPATCH = "serve.dispatch"
DEVICE_WAIT = "serve.device_wait"
FETCH = "serve.fetch"
BOOKKEEP = "serve.bookkeep"
PHASES = (ADMIT, DISPATCH, DEVICE_WAIT, FETCH, BOOKKEEP)

#: JAX's duration events for tracing a function to a jaxpr and for
#: compiling it (the latter also where the persistent cache serves it)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass(slots=True)
class ServeCounters:
    """One server's decode-loop counters. Only its worker thread writes
    them; ``*_s`` are ``time.monotonic`` seconds spent in each phase."""

    steps: int = 0      # engine steps dispatched
    rows: int = 0       # active cache rows, summed over steps
    admitted: int = 0   # requests given a cache row
    finished: int = 0   # requests retired
    admit_s: float = 0.0
    dispatch_s: float = 0.0
    device_wait_s: float = 0.0
    fetch_s: float = 0.0
    bookkeep_s: float = 0.0

    @property
    def host_s(self) -> float:
        """Loop time outside the device wait and outside parks."""
        return self.admit_s + self.dispatch_s + self.fetch_s + self.bookkeep_s

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["host_s"] = self.host_s
        return d


_lock = threading.Lock()
_compiles: Counter = Counter()
_counting = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event in COMPILE_EVENTS:
        with _lock:
            _compiles[kw.get("fun_name", "?")] += 1


def count_compiles() -> None:
    """Start counting this process's JAX traces and compiles (idempotent;
    every ``InferenceServer`` calls it)."""
    global _counting
    with _lock:
        if not _counting:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _counting = True


def compiles() -> int:
    """JAX traces and backend compiles in this process since counting
    started: a steady serving window adds none."""
    with _lock:
        return sum(_compiles.values())


def compiles_by_name() -> dict[str, int]:
    """``compiles()`` by the name of the function JAX traced or compiled."""
    with _lock:
        return dict(_compiles)
