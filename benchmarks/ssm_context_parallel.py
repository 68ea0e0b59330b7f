"""Context-parallel SSM prefill benchmark: cross-device state carry via
each exscan algorithm (8 fake CPU devices, sequence sharded).

The AFFINE ⊕ here composes (decay, state) pairs — the "expensive
operator" case where the 123-doubling algorithm's q-1 applications beat
two-⊕ doubling's ~2·log2(p).  Algorithms are pinned per run through
``ScanSpec`` (plus ``"auto"``, showing the planner's pick)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

ALGS = ("auto", "123", "1doubling", "two_op")

_CODE = """
import time, json
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.scan_api import ScanSpec
from repro.models.context_parallel import cp_ssm_scan

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(0)
B, S, D = 1, 4096, 1024
a = jnp.asarray(rng.uniform(0.9, 1.0, (B, S, D)), jnp.float32)
b = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
out = {}
for alg in %s:
    spec = ScanSpec(kind="exclusive", monoid="affine", algorithm=alg)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x, y: cp_ssm_scan(x, y, mesh, spec=spec))
        jax.block_until_ready(f(a, b))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(f(a, b))
            ts.append(time.perf_counter() - t0)
    out[alg] = min(ts) * 1e6
print("RESULT" + json.dumps(out))
"""


def run(csv_rows: list):
    env = dict(os.environ)
    # CPU-only: 8 fake devices; the child must never claim a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CODE % repr(list(ALGS))],
                          env=env, capture_output=True, text=True,
                          timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    for alg, us in res.items():
        csv_rows.append((f"cp_ssm_prefill_p8/{alg}", us,
                         "us_wallclock_cpu"))
    return csv_rows


if __name__ == "__main__":
    for r in run([]):
        print(",".join(str(x) for x in r))
