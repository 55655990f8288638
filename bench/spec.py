"""What a run is asked to do, found by name: the cell in ``BENCHMARK.json``,
its configuration under ``bench/configs/``, its traffic mix under
``bench/traffic/``, the data generator the configuration names under
``bench/gen/``, its dataset kind under ``bench/kinds/``, its per-layer
metric readers under ``bench/metrics/``, and the chip's peaks in
``bench/peaks.json``.

Nothing here knows a cell, a configuration, a mix, a generator, a kind
or a metric by name: a later change adds one with new files and
``BENCHMARK.json`` entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """The benchmark's files do not describe the requested run."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic mix's parameters
    end_to_end: tuple[dict, ...]  # the metric entries this cell reports
    per_layer: tuple[dict, ...]


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic mix."""
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, name)),
    )


def _module(path: Path, what: str, mod_name: str):
    """The Python file at ``path``, loaded as a module; ``what`` names
    it in the error when the file is missing."""
    if not path.is_file():
        raise SpecError(f"{what} has no file at {path}")
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_name] = mod  # a dataclass looks its module up there
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py", f"per-layer metric {name!r}",
                   f"bench_metric_{name}").read


def generator(name: str, root: Path = ROOT):
    """The data generator ``bench/gen/<name>.py``: ``rows(data, seed)``
    and ``TINY``, the sizes a CPU test runs it at."""
    return _module(root / "bench" / "gen" / f"{name}.py", f"data generator {name!r}",
                   f"bench_gen_{name}")


def kind(name: str, root: Path = ROOT):
    """The dataset kind ``bench/kinds/<name>.py`` (see ``bench/kinds``)."""
    return _module(root / "bench" / "kinds" / f"{name}.py", f"dataset kind {name!r}",
                   f"bench_kind_{name}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in bench/peaks.json "
                        f"(have {sorted(table)})")
    return table[device_kind]
