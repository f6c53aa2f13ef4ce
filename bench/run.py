"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root on a machine with the chips the cell asks
for. It warms up every program the window runs, serves the cell's traffic
for ``--seconds``, drains, frees the servers, then checks a seeded sample
of what was served against the plain reference. The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``check``: each
compared number beside its limit, which also end stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import entry  # noqa: E402  (environment first, before numpy and JAX)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    try:
        import repro  # noqa: F401  (the system under test, under src/)
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    bench = harness.load_benchmark()
    w, _, _ = harness.cell_spec(bench, args.workload)
    if not entry.tpus(w["chips"]):
        return 2
    out, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, bench=bench)
    for k, c in out["check"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
