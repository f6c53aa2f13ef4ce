"""The one traffic generator: a mix file under ``bench/traffic/`` in, the
requests of one run out.

Every seed gets the same multiset of sizes and, in an open loop, of
inter-arrival gaps, in another order: lengths are the distribution's
quantiles at ``(i + 0.5) / n``, gaps the exponential's quantiles scaled so
that exactly ``n = rate * seconds`` arrivals fall in the window. The seed
only orders them, picks the token ids, and pairs requests with servers.

An open loop's orders are not plain shuffles but Owen-scrambled radical
inverses (``stratified_order``), a different base for gaps, prompt
lengths, output lengths and servers: every stretch of the window then
holds close to its share of short and long gaps and requests, and the
seed moves only which ones. Under a plain shuffle one seed put more
arrivals or more long requests near the window's close than another, and
the tokens completed in a window swung by some 4% on the draw alone.
So two seeds ask for the same work and their runs differ by the system's
own noise, not by the draw.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""

    prompt: list[int]
    max_new: int
    server: int
    #: seconds after the window opens (open loop); None in a closed loop,
    #: where a client sends its next request when the last one finished
    due: Optional[float] = None
    client: Optional[int] = None


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped length distribution."""
    ps = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = NormalDist(math.log(spec["median"]), spec["sigma"])
        xs = np.array([math.exp(nd.inv_cdf(p)) for p in ps])
    elif spec["dist"] == "fixed":
        xs = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(xs), spec["min"], spec["max"]).astype(np.int64)


def stratified_order(n: int, base: int, rng) -> np.ndarray:
    """A seeded permutation of ``range(n)``: position ``i`` gets the rank
    of the Owen-scrambled radical inverse of ``i`` in ``base``. Each aligned
    run of ``base**j`` positions draws from that many strata of the ranks,
    so ``sorted_values[stratified_order(...)]`` spreads small and large
    values evenly over the positions. Different bases keep two such orders
    from moving in step."""
    digits = 1
    while base ** digits < n:
        digits += 1
    perms: dict[tuple, np.ndarray] = {}
    u = np.empty(n)
    for i in range(n):
        v, prefix, x = i, (), 0.0
        for j in range(digits):
            d = v % base
            v //= base
            p = perms.get(prefix)
            if p is None:
                p = perms[prefix] = rng.permutation(base)
            x += p[d] / base ** (j + 1)
            prefix += (d,)
        u[i] = x
    return np.argsort(np.argsort(u, kind="stable"), kind="stable")


def arrival_times(spec: dict, seconds: float, rng) -> np.ndarray:
    """Open-loop due times in ``[0, seconds)``: a Poisson process held to
    exactly ``round(rate * seconds)`` arrivals, its gaps in a stratified
    order."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    n = max(1, round(spec["rate_per_s"] * seconds))
    ps = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-ps)
    gaps = gaps[stratified_order(n, 2, rng)]
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def plan(mix: dict, *, seed: int, seconds: float, vocab: int,
         n_servers: int) -> list[Planned]:
    """The requests of one run. Open loop: those due in the window, in
    order of due time. Closed loop: a pool the clients draw from in order
    (``arrivals.pool`` requests in blocks of ``arrivals.clients``, reused
    round-robin if a run outlasts it)."""
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    if arr["loop"] == "open":
        due = arrival_times(arr, seconds, rng)
        n = len(due)
        prompts = length_quantiles(mix["prompt_len"], n)[
            stratified_order(n, 3, rng)]
        outs = length_quantiles(mix["output_len"], n)[
            stratified_order(n, 5, rng)]
        servers = np.sort(np.arange(n) % n_servers)[
            stratified_order(n, 7, rng)]
    elif arr["loop"] == "closed":
        due = None
        k = int(arr["clients"])
        n = int(arr["pool"]) // k * k
        # every block of ``clients`` requests holds the same lengths, so a
        # window that takes the first few blocks asks for the same work on
        # every seed
        prompts = np.concatenate([
            rng.permutation(length_quantiles(mix["prompt_len"], k))
            for _ in range(n // k)])
        outs = np.concatenate([
            rng.permutation(length_quantiles(mix["output_len"], k))
            for _ in range(n // k)])
        servers = rng.permutation(np.arange(n) % n_servers)
    else:
        raise ValueError(f"unknown loop {arr['loop']!r}")
    reqs = []
    for i in range(n):
        reqs.append(Planned(
            prompt=rng.integers(0, vocab, int(prompts[i])).tolist(),
            max_new=int(outs[i]),
            server=int(servers[i]),
            due=None if due is None else float(due[i]),
        ))
    return reqs
