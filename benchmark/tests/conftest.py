import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def run_cli(args, root=ROOT, env=None, timeout=900):
    """(returncode, stdout lines, stderr) of ``benchmark/run.py`` under ``root``."""
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    p = subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
                       cwd=root, env=e, capture_output=True, text=True, timeout=timeout)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def rehearse(workload, seed=1, seconds=4, trace=0, root=ROOT, env=None):
    """The result line of a CPU rehearsal run."""
    rc, out, err = run_cli(["--workload", workload, "--seed", str(seed), "--seconds",
                            str(seconds), "--trace", str(trace), "--rehearse"], root, env)
    assert rc == 0, err[-3000:]
    return json.loads(out[-1])
