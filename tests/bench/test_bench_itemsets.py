"""The itemset one-shot cell, tiny, through the whole harness on the CPU:
a sound run is correct; the bf16 control and each planted fault are not."""

from __future__ import annotations

import pytest
from benchutil import run_tiny

CELL = "itemsets.oneshot"


def test_sound_run_is_correct_and_reports_its_metrics():
    res = run_tiny(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] % 6 == 0 and res["failed"] == 0  # whole passes of two rounds
    assert set(res["metrics"]) == {"setup_s", "job_s.itemsets"}
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_reads_its_per_layer_metrics():
    res = run_tiny(CELL, trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["miner.count_calls_per_job"]["value"] > 0
    assert "idle_share.itemsets" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("control", ["bf16", "answer_altered", "half_batch"])
def test_control_and_faults_are_not_correct(control):
    res = run_tiny(CELL, control=control)
    assert res["correct"] is False, (control, res["checks"])
