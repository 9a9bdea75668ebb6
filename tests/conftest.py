"""Test configuration.

Tests run on the CPU with 8 virtual devices, so the multi-chip sharding
logic is exercised without a chip (``chip_smoke.py`` is what runs on
one).  The environment must be set before jax is imported anywhere.  The
compilation cache follows the same rule as every entry point
(utils/jaxenv.py): ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

from reporter_tpu.utils.jaxenv import enable_compilation_cache  # noqa: E402

enable_compilation_cache()
