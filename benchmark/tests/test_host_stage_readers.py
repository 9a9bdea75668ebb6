"""The six host-stage readers on synthetic snapshots: each reads the delta of
its reporter_host_stage_seconds_total labels over the window, and nothing
from a program that counts no such stage."""

import os

import pytest

import common
from conftest import BENCH

NAME = "reporter_host_stage_seconds_total"


def _snap(stages: dict, batches: float = 0.0) -> dict:
    return {NAME: {"kind": "counter", "samples": [[[k], v] for k, v in stages.items()]},
            "reporter_microbatch_batches_total": {"kind": "counter",
                                                  "samples": [[[], batches]]}}


S0 = {"parse": 1.0, "pack": 2.0, "dispatch": 0.5, "fetch": 3.0, "associate": 4.0,
      "report": 5.0, "tile": 1.0, "read": 2.0, "serialize": 0.5}
S1 = {"parse": 1.5, "pack": 2.5, "dispatch": 0.7, "fetch": 3.4, "associate": 4.8,
      "report": 6.2, "tile": 1.3, "read": 2.6, "serialize": 0.7}
# the parent program: association counted as "collect", no fetch/report/tile/read
PARENT = {"parse": 1.5, "pack": 2.5, "dispatch": 0.7, "collect": 4.8, "serialize": 0.7}


def _rec(s0, s1, **kw):
    rec = {"attempted": 110, "failed": 10, "points": 2_000_000,
           "snap0": _snap(s0, batches=20.0), "snap1": _snap(s1, batches=40.0)}
    rec.update(kw)
    return rec


def _reader(name):
    return common.load_module(os.path.join(BENCH, "metrics", name + ".py"), name)


CASES = [
    # name, expected value on (S0 -> S1)
    ("report_host_ms_per_req.stream", 1.2 * 1e3 / 100),
    ("associate_host_ms_per_req.stream", 0.8 * 1e3 / 100),
    ("fetch_wait_ms_per_batch.stream", 0.4 * 1e3 / 20),
    ("associate_host_s_per_mpt.backfill", 0.8 / 2.0),
    ("phase2_host_s_per_mpt.backfill", (0.6 + 1.2 + 0.3) / 2.0),
    ("fetch_wait_s_per_mpt.backfill", 0.4 / 2.0),
]


@pytest.mark.parametrize("name,want", CASES)
def test_reader_reads_the_window_delta(name, want):
    assert _reader(name).read(_rec(S0, S1)) == pytest.approx(want)


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_reader_reads_nothing_from_the_parent_program(name):
    assert _reader(name).read(_rec(PARENT, PARENT)) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_reader_reads_nothing_without_work(name):
    rec = _rec(S0, S1, attempted=5, failed=5, points=0)
    rec["snap1"]["reporter_microbatch_batches_total"]["samples"] = [[[], 20.0]]
    assert _reader(name).read(rec) is None
