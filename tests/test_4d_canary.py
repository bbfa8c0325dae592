"""DEFAULT-suite 4D parity canary (VERDICT round-5 #6): the full
pipe:2 x model:2 x seq:2 x data:2 composition must hold exact serial
parity on every fast-suite run, not only under --runslow — the flagship
composition used to be guarded exclusively by slow twins, so it could
regress silently between --runslow runs.

Same spawned-worker pattern as tests/test_4d_full.py (16 virtual
devices need their own process), but at the smallest shapes every axis
admits plus the persistent XLA compile cache (.cache/jax unless
JAX_COMPILATION_CACHE_DIR says otherwise — utils/backend.py):
steady-state wall-clock < 8 s measured; only the first run on a fresh
checkout pays the ~16 s compile.
"""

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
WORKER = REPO / "scripts" / "fourd16_worker.py"


def test_4d_canary_16_devices_matches_serial():
    proc = subprocess.run(
        [sys.executable, str(WORKER), "--fast"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"4D canary failed:\n{proc.stdout}\n{proc.stderr}"
    )
    assert "4D16OK" in proc.stdout
