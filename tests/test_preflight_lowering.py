"""The composed-program preflight must stay green: the GPT-2-124M train
step under each remat policy, with and without the fused loss, on one chip
and on the four-chip meshes, and the ring-attention SP step AOT-COMPILE for
a v5e topology with their Mosaic kernels present (not the reference
fallbacks). Complements tests/test_tpu_lowering.py (single kernels, serve
programs) at the level of the whole step (``apex_tpu.train``).

Runs in a subprocess: the preflight pins the process to the CPU platform
at import time, which must not leak into the pytest process (reviewer
find — collection-order-dependent backend state)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow  # ~5 min subprocess; the 5 s per-kernel guard
# (test_tpu_lowering.py) stays in the default tier
def test_preflight_lowering_passes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "preflight_lowering.py")],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    assert proc.returncode == 0, (
        f"preflight failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    assert "PREFLIGHT PASS" in proc.stdout
