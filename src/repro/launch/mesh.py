"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") —
the "pod" axis carries data parallelism across pods (its collectives
traverse DCI, which is why it is a separate, outermost axis).

``make_production_mesh`` is a function (never a module-level constant)
so importing this module does not touch jax device state.
"""

from __future__ import annotations

import os

import jax

from repro.core.scan_api import CostModel, CostProfile

# Hand-guessed default α-β-γ parameters per interconnect tier (see
# DESIGN.md §7): "pod" collectives traverse DCI (higher launch latency,
# lower bandwidth) while intra-pod axes ride ICI.  These are the
# ``source="default"`` fallback — ``resolve_profile`` prefers a
# calibrated profile measured on the actual mesh (core/tune.py).
ICI_COST = CostModel(alpha=1e-6, beta=1.0 / 50e9, gamma=2.0 / 819e9)
DCI_COST = CostModel(alpha=10e-6, beta=1.0 / 12.5e9, gamma=2.0 / 819e9)

DEFAULT_PROFILE = CostProfile(
    tiers=(("dci", DCI_COST), ("ici", ICI_COST)),
    source="default", axis_tiers=(("pod", "dci"),),
    default_tier="ici")

_active_profile: CostProfile | None = None


def install_profile(profile: CostProfile | None) -> CostProfile | None:
    """Install ``profile`` as the pricing source ``axis_cost_model``
    resolves (None restores the defaults).  Returns the previously
    installed profile.  Because the plan cache keys on resolved
    pricing constants, installing a recalibrated profile invalidates
    every stale plan without an explicit cache flush."""
    global _active_profile
    prev = _active_profile
    _active_profile = profile
    return prev


def current_profile() -> CostProfile:
    """The installed (calibrated) profile, or the default one."""
    return _active_profile or DEFAULT_PROFILE


def axis_cost_model(axis_name) -> CostModel:
    """Per-axis pricing kernel: the cross-pod axis rides the "dci"
    tier, everything else "ici" — resolved from the *installed*
    profile (calibrated when one is installed, hand-guessed defaults
    otherwise).

    A stable module-level function, so it can be installed as the
    ambient planner cost model (``scan_api.use_cost_model(
    axis_cost_model)`` — train.py and dryrun.py do) and multi-axis
    plans price each sub-axis by its own interconnect.
    """
    return current_profile().for_axis(axis_name)


def mesh_fingerprint(mesh, *, processes: int | None = None,
                     local_devices: int | None = None) -> str:
    """Identity of a mesh for the calibrated-profile store: platform,
    device kind, the axis-name/size grid, and — for multi-process
    runtimes — the process topology.

    A profile fitted across N processes prices real inter-process
    hops; resolving it for a single-process mesh (or vice versa)
    would poison planning, so the fingerprint folds in the process
    count and per-process device shape whenever more than one process
    participates.  Single-process fingerprints are unchanged
    (``processes`` defaults to ``jax.process_count()``), so existing
    stored profiles stay resolvable."""
    dev = mesh.devices.flat[0]
    kind = getattr(dev, "device_kind", "unknown")
    grid = "x".join(f"{a}{mesh.shape[a]}" for a in mesh.axis_names)
    base = f"{getattr(dev, 'platform', 'unknown')}-{kind}-{grid}"
    if processes is None:
        processes = jax.process_count()
    if int(processes) > 1:
        if local_devices is None:
            local_devices = jax.local_device_count()
        base += f"-procs{int(processes)}x{int(local_devices)}"
    return base


def resolve_profile(mesh=None, directory: str | None = None,
                    fingerprint: str | None = None) -> CostProfile:
    """The best available profile for ``mesh``: a calibrated profile
    persisted under the mesh's fingerprint, else one from the
    device-free simulated calibration flow (``python -m
    repro.core.tune --simulate``), else :data:`DEFAULT_PROFILE`."""
    from repro.core import tune  # lazy: tune lazily imports this module

    fp = fingerprint or (mesh_fingerprint(mesh) if mesh is not None
                         else None)
    if fp is not None:
        prof = tune.load_profile(fp, directory)
        if prof is not None:
            return prof
    prof = tune.load_profile("simulated-default", directory)
    return prof if prof is not None else DEFAULT_PROFILE


def use_calibrated_profile(mesh=None,
                           directory: str | None = None) -> CostProfile:
    """Resolve and install the calibrated profile for ``mesh`` (falls
    back to defaults); returns the installed profile so callers can
    log its provenance."""
    prof = resolve_profile(mesh, directory)
    install_profile(prof if prof is not DEFAULT_PROFILE else None)
    return prof


def fake_device_env(n_devices: int, env=None) -> dict:
    """Environment for a subprocess that must see ``n_devices`` fake
    CPU devices (jax fixes the count at first init, so a fresh
    process is the only way to change it).

    Strips ANY inherited device-count flag first: XLA honours the
    LAST ``--xla_force_host_platform_device_count`` occurrence, so an
    ambient count (CI env, the dry-run's 512) would silently override
    the requested one.  Shared by ``tests/helpers.run_with_devices``
    and ``benchmarks/exec_bench.py``."""
    import os

    out = dict(os.environ if env is None else env)
    inherited = [f for f in out.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count=")]
    out["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={n_devices}"]
        + inherited)
    return out


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed path
    ``<repo>/.jax_cache``: the path is part of the cache key, so it
    must not move between runs.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(repo, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (drivers, tests,
    examples).  Axes are ``Auto``: the model shards through GSPMD
    propagation and ``constrain`` hints, so parameters placed with
    :meth:`Model.param_shardings` carry no sharding in their types."""
    from jax.sharding import AxisType

    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_degree(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
