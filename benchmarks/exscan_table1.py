"""Paper Table 1 / Figure 1 analogue.

The paper benchmarks MPI_Exscan vs two-⊕ doubling vs 1-doubling vs
123-doubling on a 36-node cluster over m ∈ {1..100k} MPI_LONGs
(MPI_BXOR).  Here the four algorithms run as ppermute programs:

  (a) MEASURED on an N-fake-CPU-device mesh (relative comparison only —
      one physical core executes all ranks, so times are dominated by
      per-round dispatch overhead, which is exactly the paper's
      round-count regime);
  (b) MODELED for TPU v5e pods with the α-β-γ cost model
      t = rounds·α + rounds·(m_bytes)/B_link + ops·m·γ,
      α = 1 µs/ppermute (ICI launch+hop), B = 50 GB/s, γ from 819 GB/s
      HBM streaming of the ⊕ operands.

The round/⊕ counts themselves are asserted against Theorem 1 by the
test suite; this benchmark reports the latency consequences.
"""

from __future__ import annotations

import json
import subprocess
import sys
import os

from repro.core import oracle

ALGS = ("two_op", "1doubling", "123", "native")
EMS = (1, 10, 100, 1000, 10_000, 100_000)

ALPHA = 1e-6  # per-round launch+hop latency
B_LINK = 50e9
B_HBM = 819e9

_MEASURE = """
import os, time, json
import jax, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core.scan_api import ScanSpec, scan

p = {p}
mesh = Mesh(np.array(jax.devices()).reshape(p), ("x",))
out = {{}}
for alg in {algs}:
    spec = ScanSpec(kind="exclusive", monoid="xor", algorithm=alg,
                    axis_name="x")
    for m in {ems}:
        x = np.arange(p * m, dtype=np.int64).reshape(p, m)
        f = jax.jit(shard_map(lambda v: scan(v, spec),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        f(x)  # compile+warm
        reps = 30 if m <= 1000 else 10
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        out[f"{{alg}}/{{m}}"] = min(ts) * 1e6
print("RESULT" + json.dumps(out))
"""


def modeled_us(alg: str, p: int, m: int, itemsize: int = 8) -> float:
    if alg == "native":  # all-gather + local fold
        bytes_wire = p * m * itemsize
        t = ALPHA + bytes_wire / B_LINK + (p - 1) * m * itemsize / B_HBM
        return t * 1e6
    st = oracle.verify(p, alg)
    rounds, ops = st.rounds, st.result_path_ops
    t = rounds * ALPHA + rounds * m * itemsize / B_LINK \
        + ops * 2 * m * itemsize / B_HBM
    return t * 1e6


def measured(p: int = 8) -> dict:
    env = dict(os.environ)
    # CPU-only: p fake devices; the child must never claim a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
    env["JAX_ENABLE_X64"] = "1"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = _MEASURE.format(p=p, algs=repr(list(ALGS)), ems=repr(list(EMS)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def run(csv_rows: list):
    # measured on 8 fake devices (relative; see module docstring)
    res = measured(8)
    for m in EMS:
        for alg in ALGS:
            csv_rows.append((f"exscan_measured_p8/{alg}/m{m}",
                             res[f"{alg}/{m}"], "us_wallclock_cpu"))
    # modeled for the paper's p=36 and the pod scales
    for p in (36, 256, 512):
        for m in EMS:
            for alg in ALGS:
                csv_rows.append((f"exscan_modeled_p{p}/{alg}/m{m}",
                                 modeled_us(alg, p, m), "us_abg_model"))
    return csv_rows


if __name__ == "__main__":
    rows = run([])
    for r in rows:
        print(",".join(str(x) for x in r))
