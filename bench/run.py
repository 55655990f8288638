#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``.  The run generates its data from the seed, warms up
(reported as ``setup_s``), measures for ``--seconds``, compares every
answer of the window with the plain reference, and prints one JSON
object as the last line of standard output; the numbers compared, each
beside its limit, are the last lines of standard error.  With
``--trace 1`` it reports the per-layer metrics from a profiler trace of
the window instead of the end-to-end ones.  It exits non-zero, printing
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    try:
        import repro  # the system under test, from this checkout only
    except ImportError as e:
        repro = None
        print(f"[bench] FAILED: the program is not in this checkout ({e})", file=sys.stderr)
    if repro is None or Path(repro.__file__).resolve().parents[1] != root / "src":
        sys.exit(2)
    from bench.harness import main

    sys.exit(main(t_start=T_START))
