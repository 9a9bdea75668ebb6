"""The control at a size a test run holds: the reference in bfloat16, put
in the program's place, fails each cell family's compared number on three
seeds (control.py exits 0 only then)."""

import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


@pytest.mark.parametrize("workload,extra", [("stream-mixed", ["--seconds", "10"]),
                                            ("backfill-15s", [])])
def test_control_fails(workload, extra):
    p = subprocess.run([sys.executable, os.path.join(BENCH, "control.py"), "--workload",
                        workload, "--seeds", "1,2,3", "--rehearse", *extra],
                       cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr[-2000:]
