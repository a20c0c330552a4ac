"""Force an 8-device virtual CPU mesh before any test touches JAX.

This is the standard way to test pjit/shard_map collectives without TPU
hardware (SURVEY §4).  Must run before the first backend initialization —
which is exactly now: ``force_cpu_devices`` is safe after ``import jax`` as
long as no device has been touched yet.  ``JAX_NUM_CPU_DEVICES`` is exported
too, so children the tests spawn inherit the same count unless they pin
their own.

The persistent compilation cache stays off for the suite and its children
(the entry points' ``enable_compile_cache`` only names the directory): the
tier-1 budget has no room for cache I/O, and a warm cache would hide
compile-path regressions.
"""

import os

os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from ddlpc_tpu.utils.compat import force_cpu_devices

force_cpu_devices(int(os.environ["JAX_NUM_CPU_DEVICES"]))
