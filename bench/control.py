"""Readings that set the limits of ``correct``: the program and the
control, each over several seeds, at a cell's own size, in one process.

    python3 -m bench.control --workload <cell> --seeds <s1,s2,...>
        --seconds <s> [--control bf16] [--control-seeds <n>]

The control puts the reference in the service chain's place one step
below what the configuration states (``deploy.Deployment.control_chain``;
for the ingest cell, in the tile decoder's place).  Prints one JSON line
per (arm, seed) with every compared number.  The benchmark's own runs
never run it.  Needs the TPU, as ``bench.run`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the controls on the first N seeds only")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("bench.control needs a TPU")
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    for arm in [None] + args.control:
        for seed in seeds if arm is None else seeds[:args.control_seeds]:
            out = run.run_cell(args.workload, seed, args.seconds, False,
                               control=arm, t_start=time.perf_counter())
            print(json.dumps({"workload": args.workload,
                              "arm": arm or "program", "seed": seed,
                              "correct": out["correct"],
                              "checks": out["checks"],
                              "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
