"""In-situ MoE dispatch benchmark: full MoE layer forward wall time with
each exscan algorithm driving the global-offset collective (8 fake CPU
devices, 2 data x 4 model).  The exscan runs once per MoE layer per
step, on an (E,)-int vector — the paper's small-m regime.  The sweep is
driven through ``ScanSpec`` (including ``"auto"``, which shows what the
cost-model planner picks for this payload)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

ALGS = ("auto", "123", "1doubling", "two_op", "native")

_CODE = """
import time, json
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro import configs
from repro.core.scan_api import ScanSpec
from repro.models.model import Model

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
out = {}
rng = np.random.default_rng(0)
for alg in %s:
    cfg = configs.get_smoke(
        "qwen2_moe_a2_7b",
        scan=ScanSpec(kind="exclusive", algorithm=alg))
    m = Model(cfg, mesh)
    params = m.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)), jnp.int32)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda p, t: m.forward(p, t)[0])
        jax.block_until_ready(f(params, tokens))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(f(params, tokens))
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
        csv_rows.append((f"moe_forward_p8/{alg}", us, "us_wallclock_cpu"))
    return csv_rows


if __name__ == "__main__":
    for r in run([]):
        print(",".join(str(x) for x in r))
