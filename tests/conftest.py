"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
sharding tests run without TPU hardware.

The environment is set before JAX is imported, and the config value is
updated as well (safe while no backend is initialized) in case the
variable was read already.  The chip is reached by `chip_smoke.py` and
`bench.py`, never by tests."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
