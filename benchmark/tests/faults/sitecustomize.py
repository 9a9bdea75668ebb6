"""Faults planted under the timed path for benchmark/tests/test_faults.py.

On ``PYTHONPATH`` with ``BENCH_TEST_FAULT`` set, every Python process of the
run (the harness, the server, the batch child) patches the program as its
modules load:

  alter      report() shifts every datastore report's t1 by two minutes:
             an answer altered where it is produced
  drop_half  SegmentMatcher.match_many_async answers every other trace it
             matches with no segments: half of the batch left out
  shed       the server's micro-batcher refuses every 20th request at
             admission (429): a request left unanswered
  dup_rows   the batch pipeline buckets every window's reports twice:
             each tile row written double
  lose_window  the batch pipeline's report() fails on every 40th window:
             a window dropped
"""

import importlib.abc
import importlib.machinery
import os
import sys

FAULT = os.environ.get("BENCH_TEST_FAULT", "")


def _alter(mod):
    orig = mod.report

    def report(*a, **kw):
        out = orig(*a, **kw)
        for r in out["datastore"]["reports"]:
            r["t1"] = r["t1"] + 120.0
        return out

    mod.report = report


_seen = [0]


def _drop_half(mod):
    cls = mod.SegmentMatcher
    orig = cls.match_many_async

    def match_many_async(self, traces):
        finish = orig(self, traces)

        def half():
            out = []
            for r in finish():
                _seen[0] += 1
                out.append({"segments": []} if _seen[0] % 2 else r)
            return out

        return half

    cls.match_many_async = match_many_async


def _shed(mod):
    cls = mod.MicroBatcher
    orig = cls.submit
    n = [0]

    def submit(self, *a, **kw):
        n[0] += 1
        if n[0] % 20 == 0:
            raise mod.Overloaded("planted shed")
        return orig(self, *a, **kw)

    cls.submit = submit


def _dup_rows(mod):
    orig = mod._bucket_reports

    def bucket_twice(*a, **kw):
        orig(*a, **kw)
        orig(*a, **kw)

    mod._bucket_reports = bucket_twice


def _lose_window(mod):
    orig = mod.report_fn
    n = [0]

    def report_fn(*a, **kw):
        n[0] += 1
        if n[0] % 40 == 0:
            raise RuntimeError("planted report failure")
        return orig(*a, **kw)

    mod.report_fn = report_fn


TARGETS = {"alter": ("reporter_tpu.report.reporter", _alter),
           "drop_half": ("reporter_tpu.matching.matcher", _drop_half),
           "shed": ("reporter_tpu.serve.service", _shed),
           "dup_rows": ("reporter_tpu.batch.pipeline", _dup_rows),
           "lose_window": ("reporter_tpu.batch.pipeline", _lose_window)}


class _Hook(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        want, patch = TARGETS[FAULT]
        if name != want:
            return None
        spec = importlib.machinery.PathFinder.find_spec(name, path)
        run = spec.loader.exec_module

        def exec_module(module):
            run(module)
            patch(module)

        spec.loader.exec_module = exec_module
        return spec


if FAULT in TARGETS:
    sys.meta_path.insert(0, _Hook())
