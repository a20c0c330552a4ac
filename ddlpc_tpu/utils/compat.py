"""Process-level JAX configuration for the one installed toolchain (jax 0.9.0)."""

from __future__ import annotations

import jax


def force_cpu_devices(n: int) -> None:
    """Pin this process to an ``n``-device virtual CPU backend.

    Must run before the first device use (it is fine after ``import jax``).
    The config option outranks an inherited ``JAX_NUM_CPU_DEVICES`` or
    ``--xla_force_host_platform_device_count``, so a child of the test
    suite (conftest's 8) still gets the count IT asked for.
    """
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))
