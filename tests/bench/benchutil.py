"""Shared helpers of the benchmark's tests: tiny versions of each cell,
run through the whole harness on the CPU (no look for a chip)."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(name: str, *, root: Path = ROOT):
    """(config, mix) of cell ``name`` cut to a size a test run holds: its
    generator's ``TINY`` sizes, and four sites."""
    from bench import spec

    cell = spec.load_cell(name, root)
    cfg = copy.deepcopy(cell.config)
    cfg["data"].update(spec.generator(cfg["data"]["generator"], root).TINY)
    cfg["service"]["n_sites"] = 4
    return cfg, copy.deepcopy(cell.traffic)


def run_tiny(name: str, *, seed: int = 3, seconds: float = 1.5, trace: bool = False,
             control: str | None = None, root: Path = ROOT) -> dict:
    from bench import harness

    cfg, mix = tiny_cell(name, root=root)
    return harness.run_cell(name, seed, seconds, trace, t_start=time.perf_counter(),
                            require_tpu=False, root=root, config=cfg, traffic_mix=mix,
                            control=control)
