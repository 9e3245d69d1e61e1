"""Repo-root conftest: force tests onto a virtual 8-device CPU mesh.

The reference's test strategy (SURVEY.md §4) runs multi-rank semantics tests
without a cluster (torch MultiThreadedTestCase / MultiProcessTestCase,
torch/testing/_internal/common_distributed.py:874,1443). The JAX analog is a
host-platform device count: 8 virtual CPU devices in one process. It is set
through `XLA_FLAGS` so the worker processes the tests spawn inherit it. The
chip is reached through `chip_smoke.py`, never through pytest.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# XLA:CPU's fusion emitters OFF, for the same reason matmul precision is
# pinned below: with them on (the jax 0.9.0 default) `a*b + c` becomes one
# FMA or two roundings depending on how XLA fused the neighbours, so a
# "bitwise" assertion tests XLA's fusion choices instead of the program's
# arithmetic (tools/numlint.py `_harness_xla_flags` has the measurement).
if "xla_cpu_use_fusion_emitters" not in _flags:
    _flags += " --xla_cpu_use_fusion_emitters=false"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Determinism pin (ISSUE 18; numlint N001 cites it — the sweep and the
# bitwise parity tests assume it): jax_default_matmul_precision="highest".
# Without it, matmul accumulation dtype floats with the backend (bf16
# passes on TPU), so a "bitwise" assertion can pass on CPU and silently
# stop meaning anything on hardware. Library code on bitwise-contract
# paths must ALSO pin per call (numlint N001 enforces that); this pin is
# the TEST HARNESS's only — examples, benches and the chip run hardware-
# rate matmuls.
#
# The PRNG is NOT pinned: tests, examples, benches, spawned workers and
# the chip all draw from the installed default stream
# (`jax_threefry_partitionable=True` on jax 0.9.0), so a parity test
# exercises the stream the chip path uses.
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache: the suite is compile-dominated (hundreds
# of distinct jit programs over the 8-device mesh); caching compiled
# executables across runs turns repeat runs from ~5 min into the actual
# test-logic time. Safe to share — keyed by HLO + flags + backend.
from pytorch_distributed_example_tpu._compat import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
