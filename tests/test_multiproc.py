"""True multi-process distributed training smoke (scripts/multiproc_smoke.py).

Unlike tests/test_multihost_resume.py (which unit-tests the resume decision
protocol with a patched topology), this launches TWO real OS processes,
bootstraps them with jax.distributed via ``initialize_distributed`` — the
framework's replacement for the reference's hostname-table TCP bootstrap
(кластер.py:172-252) — builds one 8-device mesh spanning both, and trains
with the int8 ring transport crossing the process boundary.  Both ranks
must observe bit-identical losses and parameters.
"""

import os
import subprocess
import sys

SCRIPT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts",
    "multiproc_smoke.py",
)


def test_two_process_training_agrees():
    env = dict(os.environ)
    # The child processes configure their own CPU device counts; strip any
    # conftest-inherited forcing so they start clean.
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "multiproc smoke OK" in proc.stdout
