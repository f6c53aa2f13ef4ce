"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed, in one process: the cell's deployment with that seed's
weights, a short window of the cell's own traffic, then the comparison of
a sample of what was served with the plain reference, and beside it the
float8 control: the same reference with its linear layers in float8, read
at the same positions. One JSON line per seed on stdout:
``max_logit_gap`` (the program) and ``control_logit_gap`` (the control).
The benchmark's own runs never run the control.
"""

import json
import sys
import time

import entry  # environment first, before numpy and JAX


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()

    from bench import harness
    from bench import traffic as T

    if not entry.tpus():
        return 2
    harness.enable_cache()
    bench = harness.load_benchmark()
    _, conf, mix = harness.cell_spec(bench, args.workload)
    arch = harness.arch_config(conf)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        reqs = T.plan(mix, seed=seed, seconds=args.seconds,
                      vocab=conf["vocab"],
                      n_servers=mix["deployment"]["servers"])
        dep = harness.Deployment(arch, conf, mix, seed)
        dep.start()
        dep.warm()
        setup_s = time.monotonic() - t
        drv = harness.drive(dep, mix, reqs, args.seconds)
        dep.stop_cojob()
        dep.shutdown()
        t_ref = time.monotonic()
        chk = harness.check(conf, seed, drv["sent"],
                            mix["check"]["sample_tokens"], control=True)
        rec = harness.client_record(drv, args.seconds)
        print(json.dumps({"seed": seed, **chk,
                          "attempted": rec["attempted"],
                          "failed": rec["failed"],
                          "setup_s": setup_s,
                          "reference_s": time.monotonic() - t_ref,
                          "run_s": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
