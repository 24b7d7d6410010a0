"""Test harness config: fake an 8-device TPU-like topology on CPU.

This is the JAX-native answer to "test multi-chip without a cluster"
(SURVEY.md §4): the same sharded programs that run over ICI on a pod compile
and execute on 8 virtual CPU devices.

The platform is pinned with ``jax.config.update`` (it wins over whatever
``JAX_PLATFORMS`` says, so the suite is CPU-only even on a machine with a
chip), and the XLA_FLAGS device-count flag is read at (lazy) CPU-client
creation, which happens later.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
