"""The mesh cell's fault on four virtual CPU devices (its own process:
the device count is fixed when JAX starts)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_mesh_without_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), str(ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-m", "bench.tests.mesh_check"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out
    assert out["sound"]["checks"]["compared_requests"] > 0
    assert not out["no_exchange"]["correct"], out
    assert out["no_exchange"]["checks"]["mismatched_requests"] > 0
