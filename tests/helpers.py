"""Shared test helpers.

Multi-device collective tests must run in a subprocess: jax fixes the
device count at first initialization, and the main pytest process is
required to see exactly ONE CPU device (smoke tests and benches depend
on that).  ``run_with_devices`` executes a python snippet with
``--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_with_devices(code: str, n_devices: int = 8, x64: bool = True,
                     timeout: int = 600) -> str:
    """Run ``code`` in a fresh interpreter with N fake CPU devices.

    Raises on non-zero exit; returns captured stdout.
    """
    # the ambient-flag scrub lives with the mesh helpers so benchmarks
    # spawn fake-device subprocesses through the same recipe
    from repro.launch.mesh import fake_device_env

    env = fake_device_env(n_devices)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout

