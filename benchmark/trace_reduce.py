"""A profiler trace reduced to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
(chrome trace format).  Device planes are the processes whose name carries
``TPU``; their "XLA Ops" thread holds one event per executed device
operation.  From those:

  busy_s       the union of the device-operation intervals, averaged over
               the devices
  window_s     the traced window: first to last event of the whole trace
  stages_s     device time per program stage, the innermost ``rs.<stage>``
               scope named in the event (the program's kernel names)
  ops          per operation (name and HLO text, so one name in two programs
               is two entries): stage, count, device seconds and the first
               event's arguments (shapes live there)
  device_ops   the ten device operations (by stage where one is named)
               that took most time
  idle_gaps    the gaps in the first device's busy union, each named by the
               host event that overlaps it most, summed per name, longest
               first

A trace with no device plane is an error (``NoDevicePlane``), never a zero:
a CPU run has no device time to report.  The parsing of stage scopes copies
``reporter_tpu/obs/attrib.py`` (``_SCOPE_RE``, innermost match wins).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

SCOPE_RE = re.compile(r"rs\.([A-Za-z0-9_-]+)")
NUM_SUFFIX = re.compile(r"[.:]\d+$")


class NoDevicePlane(ValueError):
    pass


def stage_of(name: str, args: dict):
    for t in (name, *args.values()):
        hits = SCOPE_RE.findall(str(t))
        if hits:
            return hits[-1]
    return None


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_events(events) -> dict:
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = str((e.get("args") or {}).get("name", ""))
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = str((e.get("args") or {}).get("name", ""))
    dev = sorted(p for p, n in procs.items() if "TPU" in n)
    if not dev:
        raise NoDevicePlane("no device plane in the trace (processes: %s)"
                            % sorted(set(procs.values())))
    busy = {p: [] for p in dev}
    host = []
    lo, hi = float("inf"), float("-inf")
    stages, ops = {}, {}
    for e in events:
        if e.get("ph") != "X":
            continue
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        lo, hi = min(lo, ts), max(hi, ts + dur)
        pid = e.get("pid")
        if pid in busy:
            if threads.get((pid, e.get("tid"))) != "XLA Ops":
                continue
            busy[pid].append((ts, ts + dur))
            name = str(e.get("name", ""))
            args = e.get("args") or {}
            st = stage_of(name, args)
            key = (name, str(args.get("long_name", "")))
            rec = ops.setdefault(key, {"name": name, "stage": st, "count": 0, "dur_s": 0.0,
                                       "args": args})
            rec["stage"] = rec["stage"] or st
            rec["count"] += 1
            rec["dur_s"] += dur * 1e-6
        elif dur > 0:
            host.append((ts, ts + dur, str(e.get("name", ""))))
    for rec in ops.values():
        if rec["stage"]:
            stages[rec["stage"]] = stages.get(rec["stage"], 0.0) + rec["dur_s"]
    unions = {p: _union(iv) for p, iv in busy.items()}
    busy_s = sum(sum(b - a for a, b in u) for u in unions.values()) / len(dev) * 1e-6
    top = {}
    for rec in ops.values():
        key = "rs." + rec["stage"] if rec["stage"] else NUM_SUFFIX.sub("", rec["name"])
        top[key] = top.get(key, 0.0) + rec["dur_s"]
    gaps, at = [], lo
    for a, b in unions[dev[0]] + [[hi, hi]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    named = {}
    host.sort()
    active, k = [], 0
    for a, b in gaps:  # sweep: host events that overlap each gap
        while k < len(host) and host[k][0] < b:
            active.append(host[k])
            k += 1
        active = [h for h in active if h[1] > a]
        best, who = 0.0, "(no host event)"
        for h0, h1, n in active:
            ov = min(b, h1) - max(a, h0)
            if ov > best:
                best, who = ov, n
        named[who] = named.get(who, 0.0) + (b - a) * 1e-6
    return {"devices": len(dev), "busy_s": busy_s, "window_s": (hi - lo) * 1e-6,
            "stages_s": stages, "ops": ops,
            "device_ops": sorted(([k, v] for k, v in top.items()), key=lambda kv: -kv[1]),
            "idle_gaps": sorted(([k, v] for k, v in named.items()), key=lambda kv: -kv[1])}


def trace_files(trace_dir: str):
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the one trace file a capture wrote under ``trace_dir``."""
    paths = trace_files(trace_dir)
    if len(paths) != 1:
        raise ValueError("expected one *.trace.json.gz under %s, found %d"
                         % (trace_dir, len(paths)))
    with gzip.open(paths[0]) as f:
        return reduce_events(json.load(f).get("traceEvents", []))
