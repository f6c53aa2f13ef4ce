"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read, with nothing but ``jax.profiler.ProfileData``.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line holds
every operation the chip ran and the ``XLA Modules`` line every program
execution. The traced window is the benchmark's own host span
``bench.traced_window``; everything is clipped to it.

- ``busy_s``: the union of operation intervals in the window, averaged
  over the device planes;
- ``steps_s``: each serve-step execution's device time (a module whose
  name holds ``serve_step``);
- ``step_gaps_s``: for consecutive serve-step executions on one chip, the
  time between them in which no operation ran;
- ``device_ops``: the operations that took most device time, by HLO name;
- ``idle_gaps``: device idle time in the window by what the host threads
  that dispatch device work were doing then: the name of their event that
  overlaps the gap most, or ``unattributed`` where none does (Python code
  of the decode loop carries no span yet).
"""

from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from pathlib import Path
from typing import Iterable

WINDOW_SPAN = "bench.traced_window"
STEP = "serve_step"
#: host threads with such events dispatch device work
DISPATCH = "PjitFunction("
TOP = 10


def union(iv: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: list[tuple[float, float]], a: float, b: float) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in merged)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    spans, host_events = [], []
    for ln in (ln for pl in host for ln in pl.lines):
        evs = list(_events(ln))
        spans += [(a, b) for n, a, b in evs if n == WINDOW_SPAN]
        if any(n.startswith(DISPATCH) for n, _, _ in evs):
            host_events += evs
    if not spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    w0, w1 = spans[0]
    host_events = [(n, a, b) for n, a, b in host_events if b > w0 and a < w1]

    busy, steps, gaps = 0.0, [], []
    op_time: dict[str, float] = defaultdict(float)
    idle_by: dict[str, float] = defaultdict(float)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = [(n, max(a, w0), min(b, w1))
               for n, a, b in _events(lines["XLA Ops"]) if b > w0 and a < w1]
        for n, a, b in ops:
            op_time[n.split(" = ")[0]] += b - a
        merged = union((a, b) for _, a, b in ops)
        busy += sum(b - a for a, b in merged)
        mods = sorted((a, b) for n, a, b in _events(lines["XLA Modules"])
                      if STEP in n and a >= w0 and b <= w1)
        steps += [b - a for a, b in mods]
        for (_, e), (s, _) in zip(mods, mods[1:]):
            gaps.append(max(0.0, (s - e) - covered(merged, e, s)))
        idle, prev = [], w0
        for a, b in merged + [(w1, w1)]:
            if a > prev:
                idle.append((prev, a))
            prev = max(prev, b)
        for (a, b), name in zip(idle, _attribute(host_events, idle)):
            idle_by[name] += b - a
    n = max(1, len(devices))
    return {
        "devices": len(devices),
        "window_s": w1 - w0,
        "busy_s": busy / n,
        "steps_s": steps,
        "step_gaps_s": gaps,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(idle_by.items(), key=lambda kv: -kv[1])[:TOP],
    }


def _attribute(host_events, gaps: list) -> list[str]:
    """For each of the sorted, disjoint ``gaps``, the name of the host
    event that overlaps it most."""
    ends = [b for _, b in gaps]
    best = [0.0] * len(gaps)
    names = ["unattributed"] * len(gaps)
    for n, x, y in host_events:
        i = bisect.bisect_right(ends, x)
        while i < len(gaps) and gaps[i][0] < y:
            o = min(y, gaps[i][1]) - max(x, gaps[i][0])
            if o > best[i]:
                best[i], names[i] = o, n
            i += 1
    return names
