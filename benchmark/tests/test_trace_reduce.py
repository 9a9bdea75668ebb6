"""trace_reduce against a small recorded trace (a stretch of a backfill-15s
capture on the v5e, PR 22: 30 device operations and the host spans over
them) and against hand-made events."""

import gzip
import json
import math
import os
import re

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorded():
    with gzip.open(os.path.join(DATA, "tpu_v5e_backfill.trace.json.gz")) as f:
        return json.load(f)["traceEvents"]


def test_recorded_tpu_trace():
    r = trace_reduce.reduce_events(_recorded())
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(7.429376e-06, rel=1e-9)
    assert r["window_s"] == pytest.approx(1.0117507800e-04, rel=1e-9)
    assert r["stages_s"] == pytest.approx(
        {"compact-gather": 1.93672e-07, "select": 1.423516e-06, "candidate-sweep": 1.51375e-06},
        rel=1e-9)
    assert [k for k, _v in r["device_ops"]] == [
        "broadcast_in_dim", "rs.candidate-sweep", "rs.select", "copy-done", "pad_add_fusion",
        "rs.compact-gather", "copy-start"]
    assert len(r["idle_gaps"]) == 1
    name, secs = r["idle_gaps"][0]
    assert name == "CommonPjRtLoadedExecutable::Execute"
    assert secs == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)


def _meta(pid, name, tid=None, thread=None):
    out = [{"ph": "M", "pid": pid, "name": "process_name", "args": {"name": name}}]
    if thread:
        out.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                    "args": {"name": thread}})
    return out


def _op(pid, tid, ts, dur, name, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur, "name": name, "args": args}


def test_union_stages_and_gaps():
    ev = (_meta(1, "/device:TPU:0", 7, "XLA Ops") + _meta(2, "/host:CPU", 9, "main")
          + [_op(1, 7, 0, 10, "fusion.1", tf_op="jit(f)/rs.ubodt-probe/gather"),
             _op(1, 7, 5, 10, "fusion.2", tf_op="jit(f)/rs.transition-build/rs.select/x"),
             _op(1, 7, 30, 10, "copy.3"),
             _op(2, 9, 14, 20, "pack"), _op(2, 9, 40, 20, "report")])
    r = trace_reduce.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(25e-6)      # [0,15] + [30,40]
    assert r["window_s"] == pytest.approx(60e-6)    # first event 0, last ends 60
    assert r["stages_s"] == pytest.approx({"ubodt-probe": 10e-6, "select": 10e-6})
    assert dict(r["idle_gaps"]) == pytest.approx({"pack": 15e-6, "report": 20e-6})


def test_no_device_plane_is_an_error():
    ev = _meta(2, "/host:CPU", 9, "main") + [_op(2, 9, 0, 5, "pack")]
    with pytest.raises(trace_reduce.NoDevicePlane):
        trace_reduce.reduce_events(ev)


def test_probe_rows_are_executed_rows():
    """The roofline's gathered rows against the copied ``executed_rows``
    model over the dispatched shapes, on an excerpt of the recorded v5e trace
    (each probe-stage operation of a backfill-15s capture once): every
    dispatch carries one pair-key tensor [B, T-1, K, K, 1] per probe of the
    cuckoo layout's two."""
    with gzip.open(os.path.join(DATA, "tpu_v5e_ubodt_probe.trace.json.gz")) as f:
        r = trace_reduce.reduce_events(json.load(f)["traceEvents"])
    import common
    roof = common.load_module(os.path.join(os.path.dirname(DATA), "..", "metrics",
                                           "ubodt_probe_roofline.py"), "roof")
    probe = [o for o in r["ops"].values() if o["stage"] == "ubodt-probe"]
    gathered = sum(roof.row_bytes(o["args"]["long_name"]) * o["count"] for o in probe)
    shapes = {}
    for o in probe:
        m = re.search(r"= s32\[([\d,]+)\]", o["args"]["long_name"])
        if o["name"].startswith("compare_select_fusion") and m:
            dims = tuple(int(d) for d in m.group(1).split(","))
            shapes[dims] = shapes.get(dims, 0) + o["count"]
    assert all(c % 2 == 0 for c in shapes.values())  # two probes per dispatch
    model = sum(roof.executed_rows(math.prod(d), 2) * (c // 2) for d, c in shapes.items())
    assert gathered == model * 128 * 4  # rows of 128 u32 words
    assert model == 5801856
    rec = {"trace": r, "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
           "device": {"kind": "TPU v5 lite"}}
    assert roof.read(rec) == pytest.approx(
        100.0 * model * 512 / 819e9 / r["stages_s"]["ubodt-probe"], rel=1e-12)
