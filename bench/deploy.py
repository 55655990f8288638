"""A configuration's deployment: its data, made from the run's seed, and
the service built with the configuration's constructor arguments.

A configuration file names its dataset's ``kind``, the generator and its
parameters under ``data`` and the ``MiningService`` arguments under
``service``.  Both are found by name: the generator at
``bench/gen/<generator>.py``, the kind at ``bench/kinds/<kind>.py``
(``bench.spec``).  The data generators are the benchmark's own, so the
yardstick does not move when the program's generators change.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import spec
from bench.spec import ROOT


@dataclass
class Deployment:
    config: dict
    service: object  # repro.launch.serve.MiningService
    dataset: str
    rows: np.ndarray  # the dataset as loaded
    kind: object  # the dataset kind's module (bench/kinds/<kind>.py)


def make_rows(config: dict, seed, root: Path = ROOT) -> np.ndarray:
    """The configuration's rows from ``seed`` (an int or a sequence of ints)."""
    return spec.generator(config["data"]["generator"], root).rows(config["data"], seed)


def build(config: dict, seed: int, root: Path = ROOT) -> Deployment:
    """Generate the data from ``seed``, build the service, register and
    load the dataset."""
    from repro.launch.serve import MiningService

    kind = spec.kind(config["kind"], root)
    rows = make_rows(config, seed, root)
    svc = MiningService(**config["service"])
    kind.load(svc, config["dataset"], config["data"], rows)
    return Deployment(config=config, service=svc, dataset=config["dataset"], rows=rows,
                      kind=kind)
