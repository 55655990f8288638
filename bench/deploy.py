"""A configuration's deployment: its data, made from the run's seed, and
the service built with the configuration's constructor arguments.

A configuration file names its dataset's ``kind`` (``transactions``),
the generator and its parameters under ``data`` and the
``MiningService`` arguments under ``service``.  The data generators are
the benchmark's own (``bench/gen``), so the yardstick does not move when
the program's generators change.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.gen.quest import quest_patterns, quest_transactions


@dataclass
class Deployment:
    config: dict
    service: object  # repro.launch.serve.MiningService
    dataset: str
    rows: np.ndarray  # the dataset as loaded


def make_rows(config: dict, seed) -> np.ndarray:
    """The configuration's rows from ``seed`` (an int or a sequence of ints)."""
    d = config["data"]
    if d["generator"] == "quest":
        pats = quest_patterns(d["pattern_seed"], d["n_items"], d["n_patterns"],
                              d["avg_pattern_len"], correlation=d["correlation"],
                              corruption_mean=d["corruption_mean"],
                              corruption_var=d["corruption_var"])
        return quest_transactions(seed, d["n_tx"], d["n_items"], pats, avg_tx_len=d["avg_tx_len"])
    raise ValueError(f"unknown generator {d['generator']!r}")


def build(config: dict, seed: int) -> Deployment:
    """Generate the data from ``seed``, build the service, register and
    load the dataset."""
    from repro.launch.serve import MiningService

    rows = make_rows(config, seed)
    svc = MiningService(**config["service"])
    name, kind, d = config["dataset"], config["kind"], config["data"]
    if kind != "transactions":
        raise ValueError(f"no deployment of {kind!r} datasets")
    svc.register_dataset(name, kind, n_items=d["n_items"])
    svc.append_transactions(name, rows)
    return Deployment(config=config, service=svc, dataset=name, rows=rows)
