"""Offered-load sweep of an open-loop cell, to find the highest rate the
deployment sustains (its knee). Run once when a cell is made; the cell's
mix then states its rate as a number.

    python3 bench/sweep.py --workload <cell> --rates 10,20,30 --seconds 15

One deployment serves each rate in turn for ``--seconds`` and drains;
one JSON line per rate: tokens/s, first-token and token-gap tails, and
how many requests were still unfinished when the window closed.
"""

import json
import sys

import entry  # environment first, before numpy and JAX


def main() -> int:
    import argparse
    import copy

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()

    from bench import harness
    from bench import traffic as T
    from bench.stats import pct

    if not entry.tpus():
        return 2
    harness.enable_cache()
    bench = harness.load_benchmark()
    _, conf, mix = harness.cell_spec(bench, args.workload)
    dep = harness.Deployment(harness.arch_config(conf), conf, mix, args.seed)
    dep.start()
    dep.warm()
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            m = copy.deepcopy(mix)
            m["arrivals"]["rate_per_s"] = rate
            reqs = T.plan(m, seed=args.seed, seconds=args.seconds,
                          vocab=conf["vocab"],
                          n_servers=m["deployment"]["servers"])
            drv = harness.drive(dep, m, reqs, args.seconds)
            rec = harness.client_record(drv, args.seconds)
            open_at_close = sum(
                1 for s in drv["sent"]
                if not s.output.times or s.output.times[-1] >= drv["t1"])
            print(json.dumps({
                "rate_per_s": rate,
                "tokens_per_s": rec["tokens_in_window"] / args.seconds,
                "ttft_p50_s": pct(rec["ttft_s"], 0.5),
                "ttft_p95_s": pct(rec["ttft_s"], 0.95),
                "itl_p95_s": pct(rec["itl_s"], 0.95),
                "attempted": rec["attempted"], "failed": rec["failed"],
                "open_at_close": open_at_close,
                "drain_s": drv["drained"] - drv["t1"],
                "cojob_gflop_per_s": drv["counters"]["cojob_done"]
                * (dep.cojob.flop if dep.cojob else 0) / args.seconds / 1e9,
            }), flush=True)
    finally:
        dep.stop_cojob()
        dep.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
