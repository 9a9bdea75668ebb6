"""Where the persistent compilation cache goes (utils/jaxenv.py): the
directory ``$JAX_COMPILATION_CACHE_DIR`` names and nowhere else, else the
fixed ``<checkout>/.jax_cache``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = """
import json, jax, jax.numpy as jnp
from reporter_tpu.utils.jaxenv import ensure_platform
where = ensure_platform()
d = jax.config.jax_compilation_cache_dir
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if %(compile)s:
    jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0)).block_until_ready()
from reporter_tpu.utils.jaxenv import enable_compilation_cache
print(json.dumps({"config": d, "returned": enable_compilation_cache()}))
"""


def _run(env_updates, compile_, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_updates, HOME=str(tmp_path / "home"),
               PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"compile": compile_}], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "xla"
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(cache)}, True, tmp_path)
    assert got == {"config": str(cache), "returned": str(cache)}
    assert os.listdir(cache), "the compile landed nowhere"
    # nothing under HOME, nothing else in the working directory
    assert not (tmp_path / "home").exists()
    assert sorted(os.listdir(tmp_path)) == ["xla"]


def test_cache_defaults_to_the_checkout(tmp_path):
    got = _run({}, False, tmp_path)
    want = os.path.join(REPO, ".jax_cache")
    assert got == {"config": want, "returned": want}
    assert not (tmp_path / "home").exists()
