"""The timed path broken underneath, the rest of a run driven on the CPU:
``correct`` must come out false for each fault a cell can have."""

import os

import pytest

from conftest import rehearse

FAULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "faults")


@pytest.mark.parametrize("workload,fault", [
    ("stream-mixed", "alter"), ("stream-mixed", "drop_half"), ("stream-mixed", "shed"),
    ("backfill-15s", "alter"), ("backfill-15s", "drop_half"), ("backfill-15s", "dup_rows"),
    ("backfill-15s", "lose_window")])
def test_fault_is_not_correct(workload, fault):
    line = rehearse(workload, seed=5, env={"PYTHONPATH": FAULTS, "BENCH_TEST_FAULT": fault})
    assert line["correct"] is False, line["checks"]
