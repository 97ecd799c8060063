"""Readings that the limits of a cell are set from, at the cell's size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        [--kinds program,control,half,alloc0,assign0] [--out <file>]

For each seed, in one process: the cell's driver sets up as a run does
(the program's own first units of work, kept), frees the program, and
the comparison reads each kind: ``program`` (what the program produced
against the reference), ``control`` (the reference in bfloat16 in the
program's place), ``half`` (the reference with half of the cohort left
out and the mean taken over the rest), ``alloc0`` (the allocation a
solver left at its start reports) and ``assign0`` (every device on the
edge after its own). One JSON line per seed and kind. The benchmark's
own runs never run this. Exits 2 off the chip.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402
from bench.drivers import _plan  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(_plan.KINDS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec, entry, cell, config = run.cell_spec(args.workload)
    run.devices_or_exit(entry["chips"])
    sys.path[:0] = [run.ROOT, os.path.join(run.ROOT, "src")]
    import jax
    run.compile_cache(jax)
    mod = importlib.import_module(f"bench.drivers.{cell['driver']}")
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            driver = mod.Driver(config, cell["params"], seed)
            setup_s = time.perf_counter() - t0
            driver.free()
            for kind in args.kinds.split(","):
                t0 = time.perf_counter()
                numbers = driver.check(kind)
                line = json.dumps({"cell": args.workload, "seed": seed,
                                   "kind": kind, "numbers": numbers,
                                   "setup_s": setup_s,
                                   "check_s": time.perf_counter() - t0})
                print(line, flush=True)
                if out:
                    print(line, file=out, flush=True)
            del driver
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
