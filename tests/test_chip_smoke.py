"""Rehearsal of ``chip_smoke.py`` on CPU.

The script accepts only a TPU and runs every kernel compiled.  These
tests drive the same phases on CPU devices, with the module's platform,
interpret flag and sizes overridden from outside the script (SMOKE
model configs, small kernel widths), and check that the script itself
refuses to run without a TPU or without the repository around it.
"""

import json
import os
import shutil
import subprocess
import sys

from helpers import REPO, run_with_devices

_REHEARSE = """
import json, os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
sys.path.insert(0, {repo!r})
import chip_smoke as cs

cs.PLATFORM = "cpu"
cs.INTERPRET = True
cs.ROWS, cs.COLS_INT, cs.COLS_F32 = 64, 128, 256
cs.MOE_T, cs.MOE_K, cs.MOE_E = 64, 8, 40
cs.CARRY = 4096
cs.SERVE_ONE = ["--arch", "granite-moe-3b-a800m", "--smoke",
                "--batch", "2", "--prompt-len", "8", "--gen", "3"]
cs.SERVE_FOUR = ["--arch", "qwen2-moe-a2.7b", "--smoke",
                 "--model-mesh", "4", "--batch", "4", "--prompt-len", "8",
                 "--gen", "3"]
cs.main({argv!r})
"""


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_one_chip_phases_rehearsed_on_cpu(tmp_path):
    out = run_with_devices(_REHEARSE.format(
        cache=str(tmp_path), repo=REPO, argv=[]), 1, x64=False)
    assert _last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = [ln for ln in out.splitlines() if "] ok " in ln]
    assert len(phases) == 8, out  # 7 kernel phases + serve
    assert f"compile_cache={tmp_path}" in out


def test_four_chip_phases_rehearsed_on_cpu(tmp_path):
    out = run_with_devices(_REHEARSE.format(
        cache=str(tmp_path), repo=REPO, argv=["--chips", "4"]), 4,
        x64=False)
    assert _last_json(out)["device"]["count"] == 4
    # 2 payloads x 6 algorithms x {scan, scan_with_total} x 2 executors,
    # three pinned serves and the token comparison
    phases = [ln for ln in out.splitlines() if "] ok " in ln]
    assert len(phases) == 48 + 3 + 1, out
    assert "plan=ring" in out and "profile=default" in out


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_cpu():
    proc = _run_script(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_fails(tmp_path):
    """Without the repository beside it the script cannot run."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(tmp_path, "chip_smoke.py")
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert '"ok"' not in proc.stdout
