"""The benchmark's own tests run on the CPU, on four or more virtual devices,
with no persistent compile cache (nothing is left behind, nothing is read
that another run compiled)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
