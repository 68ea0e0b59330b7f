"""End-to-end training driver with checkpoint/restart fault tolerance.

Usage (CPU example — the quickstart trains a ~100M model):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Production features exercised here end-to-end:
  * deterministic resumable data pipeline (seeded by step),
  * async sharded checkpointing with atomic commit,
  * automatic resume from the latest committed checkpoint,
  * straggler/step-time telemetry with EWMA watchdog,
  * planner-driven exscan for the MoE dispatch collective
    (``--exscan auto`` cost-model selection by default; explicit
    algorithms remain selectable for A/B runs).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.store import CheckpointStore
from repro.core import scan_api
from repro.core.scan_api import ScanSpec
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib
from repro.launch.steps import make_train_step
from repro.models.model import Model
from repro.optim import adamw_init


class StragglerWatchdog:
    """EWMA step-time tracker; flags steps slower than ``k`` x EWMA.

    On a real cluster the flag feeds the controller's drop-and-rebalance
    policy (DESIGN.md §10); here it provides the telemetry + hook."""

    def __init__(self, alpha: float = 0.1, k: float = 3.0):
        self.alpha = alpha
        self.k = k
        self.ewma = None
        self.flagged: list[int] = []

    def observe(self, step: int, dt: float) -> bool:
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.k * self.ewma
        if slow:
            self.flagged.append(step)
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--exscan", default="auto",
                    choices=["auto", "123", "1doubling", "two_op",
                             "native", "ring"])
    ap.add_argument("--profile-dir", default=None,
                    help="calibrated cost-profile store (default: "
                         "tune/profiles or $REPRO_PROFILE_DIR; see "
                         "python -m repro.core.tune)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--autotune", action="store_true",
                    help="online cost-profile refits: probe the "
                         "planned exscan schedule at --autotune-every "
                         "cadence, stream the timings into NNLS refits "
                         "and install recalibrated profiles past the "
                         "drift gate (repro.core.autotune)")
    ap.add_argument("--autotune-every", type=int, default=10,
                    help="steps between autotune probes")
    args = ap.parse_args(argv)

    get = configs.get_smoke if args.smoke else configs.get
    cfg = get(args.arch, scan=ScanSpec(kind="exclusive",
                                       algorithm=args.exscan))
    mesh = mesh_lib.make_host_mesh(args.data_mesh, args.model_mesh)
    # planner pricing provenance: prefer a profile calibrated on this
    # mesh (core/tune.py) over the hand-guessed defaults, and say which
    profile = mesh_lib.use_calibrated_profile(
        mesh, directory=args.profile_dir)
    prov = profile.provenance(mesh_lib.mesh_fingerprint(mesh))
    print(f"[planner] cost profile: {prov['source']} "
          f"fingerprint={prov['fingerprint']} "
          f"mesh={prov['mesh_fingerprint']}"
          + (f" fit_residuals={prov['fit_residuals']}"
             if prov["fit_residuals"] else ""))
    model = Model(cfg, mesh)

    params = model.init_params(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    start_step = 0

    store = None
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        if args.resume == "auto":
            latest = store.latest_step()
            if latest is not None:
                state = store.restore(latest, {"params": params, "opt": opt})
                params, opt = state["params"], state["opt"]
                start_step = latest
                print(f"[resume] restored step {latest}")

    step_fn = jax.jit(make_train_step(
        cfg, mesh, lr_peak=args.lr, warmup=max(1, args.steps // 20),
        total_steps=args.steps), donate_argnums=(0, 1))

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
    rng = np.random.default_rng(1234)
    watchdog = StragglerWatchdog()
    tuner = None
    if args.autotune:
        from repro.core.autotune import AutoTuner

        # the training scans run inside the jitted step, so the online
        # loop times the *planned* schedule out-of-band (tuner.probe)
        # at probe cadence; installs reprice every future plan() call
        tuner = AutoTuner(profile, mesh_fingerprint="train-online")
        probe_axes = mesh_lib.batch_axes(mesh)
        probe_spec = cfg.scan.over(
            probe_axes[-1] if probe_axes else "data", monoid="add")
        probe_p = max(2, mesh_lib.data_degree(mesh))
        probe_bytes = 8 * max(1, getattr(cfg, "n_experts", 8) or 8)
    losses = []
    # "auto" scan specs price each mesh axis by its interconnect tier
    with scan_api.use_cost_model(mesh_lib.axis_cost_model), \
            jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            batch = dict(data.batch(step))
            batch.pop("positions", None)
            batch.pop("segments", None)
            if cfg.frontend == "vision":
                batch["prefix"] = jnp.asarray(rng.standard_normal(
                    (args.batch, cfg.n_prefix, cfg.d_model)),
                    jnp.dtype(cfg.dtype))
            if cfg.frontend == "audio":
                batch = {
                    "embeds": jnp.asarray(rng.standard_normal(
                        (args.batch, args.seq, cfg.d_model)),
                        jnp.dtype(cfg.dtype)),
                    "labels": jnp.asarray(batch["labels"]),
                }
            t0 = time.time()
            params, opt, metrics = step_fn(
                params, opt, batch, jnp.int32(step))
            loss = float(metrics["loss"])
            dt = time.time() - t0
            slow = watchdog.observe(step, dt)
            losses.append(loss)
            if step % args.log_every == 0 or slow:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"ce {float(metrics['ce']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f} ms{'  [STRAGGLER]' if slow else ''}")
            if tuner is not None and step % args.autotune_every == 0:
                tuner.probe(probe_spec, probe_p, probe_bytes)
                res = tuner.maybe_refit()
                if res.installed:
                    prov = res.profile.provenance()
                    print(f"[autotune] step {step}: installed refit "
                          f"fingerprint={prov['fingerprint']} "
                          f"drift={dict(res.drift)} "
                          f"plans_dropped={res.plans_dropped}")
            if store and args.ckpt_every and \
                    (step + 1) % args.ckpt_every == 0:
                store.save(step + 1, {"params": params, "opt": opt},
                           blocking=False)
    if store:
        store.wait()
        store.save(args.steps, {"params": params, "opt": opt})
    if tuner is not None:
        print(f"[autotune] refits={tuner.refits} "
              f"installs={tuner.installs} "
              f"plans_dropped={tuner.plans_dropped} "
              f"reservoirs={tuner.reservoir_sizes()}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    mesh_lib.use_compile_cache()
    train()
