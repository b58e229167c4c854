"""Test session config: CPU, single real device (the dry-run's 512 forced
host devices are set ONLY inside launch/dryrun.py, never here)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

