"""Nearest-rank percentiles, as ``repro.core.stats.latency_summary``
takes them (copied so that the yardstick stays with the benchmark)."""

from __future__ import annotations

from typing import Optional, Sequence


def pct(xs: Sequence[float], p: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, max(0, int(round(p * (len(xs) - 1)))))]
