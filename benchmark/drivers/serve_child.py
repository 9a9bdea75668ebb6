"""The one process on the chip for the ``serve`` entry (see serve.py).

    python serve_child.py <peak file> <serve arguments ...>

Runs ``python -m reporter_tpu.serve <serve arguments>`` in this process,
unchanged.  When the server's ``main`` has returned (after SIGTERM's drain,
before the interpreter tears JAX down) it writes the device memory peak as
JAX reports it, the fullest device's ``peak_bytes_in_use``, to the peak
file: /statusz shows only the bytes in use.
"""

import json
import os
import runpy
import sys


def _peak() -> int:
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def main() -> None:
    peak_path = sys.argv[1]
    sys.argv = ["reporter_tpu.serve"] + sys.argv[2:]
    sys.path[0] = os.getcwd()  # as ``python -m`` from the checkout has it
    try:
        runpy.run_module("reporter_tpu.serve", run_name="__main__", alter_sys=True)
    finally:
        with open(peak_path, "w") as f:
            json.dump({"memory_peak_bytes": _peak()}, f)


if __name__ == "__main__":
    main()
