"""probe_compact_step_share.backfill on synthetic snapshots: Δ compact steps
over Δ all steps of reporter_ubodt_probe_steps_total, and nothing from a
program without the counter or a window that ran no step."""

import os

import pytest

import common
from conftest import BENCH

NAME = "reporter_ubodt_probe_steps_total"


def _snap(paths: dict) -> dict:
    return {NAME: {"kind": "counter", "samples": [[[k], v] for k, v in paths.items()]}}


def _reader():
    name = "probe_compact_step_share.backfill"
    return common.load_module(os.path.join(BENCH, "metrics", name + ".py"), name)


def test_reads_the_window_delta():
    s0 = _snap({"compact": 1000.0, "overflow": 3.0})
    s1 = _snap({"compact": 1990.0, "overflow": 5.0, "full": 8.0})
    assert _reader().read({"snap0": s0, "snap1": s1}) == pytest.approx(990.0 / 1000.0)


def test_reads_nothing_without_the_counter_or_steps():
    parent = {"reporter_xla_compiles_total": {"kind": "counter", "samples": [[[], 3.0]]}}
    assert _reader().read({"snap0": parent, "snap1": parent}) is None
    s = _snap({"compact": 10.0})
    assert _reader().read({"snap0": s, "snap1": s}) is None
