"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The analog of the reference's ``SGX_MODE=SW`` simulation testing
(reference .github/workflows/ci.yaml:15-16): tests never require real TPU
hardware. Multi-chip sharding tests run against
``--xla_force_host_platform_device_count=8``.

Must run before anything imports jax, hence the env mutation at module
import time (pytest imports conftest first).
"""

import os

# Force, don't setdefault: tests issue thousands of tiny dispatches and
# must never land on (or claim) an attached accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
# 8 timesliced virtual devices rendezvous slowly on a loaded CI core;
# the default terminate timeout SIGABRTs spuriously at larger test
# shapes (BIGRUN_r5.md — a flag, not a scale wall). Each flag is added
# only if the ambient XLA_FLAGS does not already set it.
for _flag in (
    "--xla_force_host_platform_device_count=8",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120",
    "--xla_cpu_collective_call_terminate_timeout_seconds=600",
):
    if _flag.split("=")[0].lstrip("-") not in _flags:
        _flags = f"{_flags} {_flag}".strip()
os.environ["XLA_FLAGS"] = _flags
