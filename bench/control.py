#!/usr/bin/env python3
"""Runs that must come out not correct, and the program's readings beside
them, several seeds in one process (set-up compiles once).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20 --control bf16

Each seed is a run of the cell without its warm-up (set-up, a window at
the cell's own load, the comparison); ``--control bf16`` compares the
reference computed in bfloat16 in the program's place, and prints the
program's own readings of the same run under ``program_checks``.  One
JSON line per seed.  The benchmark's own runs never run this.
"""

import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    import argparse

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="bf16")
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False, t_start=t0,
                                   control=args.control or None, warm=False)
        except harness.NoChip as e:
            print(f"[bench] FAILED: {e}", file=sys.stderr)
            sys.exit(2)
        res["seed"] = seed
        print(json.dumps(harness.finite(res)), flush=True)
