"""Executable schedule IR for scan plans (DESIGN.md §7).

A :class:`Schedule` is the explicit, inspectable program of a scan
algorithm: a sequence of :class:`RoundStep`s — peer offsets for the
``ppermute`` of each simultaneous send-receive round, SPMD receive
masks, the ⊕ combine direction, identity fixups — over per-rank payload
:class:`Segment`s.  Registered algorithms *build* schedules
(``build_123`` …), the planner derives its predicted round/⊕/all-gather
counts by counting the IR, and three executors run the same schedule:

  * :class:`SPMDExecutor` — one ``lax.ppermute`` per round inside
    ``shard_map`` (what ``scan_api.scan`` runs on a mesh);
  * :class:`SimulatorExecutor` — pure-numpy, rank-by-rank lockstep
    execution at any p with no devices (dry-run plan verification,
    benchmark drift checks, property tests);
  * :class:`PallasExecutor` — the SPMD executor with the per-round ⊕
    combine hook lowered through the on-chip Pallas block-combine
    kernel (``kernels/blelloch_exscan.block_combine``).

Because the planner's counts and the executors consume the *same* IR,
``ScanPlan`` predictions equal ``collect_stats()`` measurements by
construction — the IR is the single source of truth for what runs.

Three schedule *transforms* extend single algorithms into programs:

  * :func:`segment` — the paper's large-m pipelining: the p−1-round
    neighbour ring becomes p−2+S rounds of one m/S-byte segment each.
  * :func:`compose` — the DESIGN §5 multi-axis rewrite inlined into
    ONE schedule: inner exscan + minor-axis allreduce + outer exscan
    + one combining ⊕, each :class:`RoundStep` tagged with the mesh
    axis it runs over and stitched together by register control steps
    (``stage`` saves/rebinds the accumulator between phases, ``merge``
    applies the final ⊕).  Multi-axis plans therefore lower, simulate
    and Pallas-execute exactly like single-axis ones.
  * :func:`fuse` — k same-axis/same-kind scan payloads packed into one
    flattened buffer described by a :class:`PayloadLayout`, so all k
    scans ride the SAME q rounds (α·q once instead of k·α·q) and are
    unpacked afterwards.

``with_total``/``build_scan_total`` additionally fuse an exclusive
scan with an allreduce of the same payload ("scan_total" kind): for
power-of-two p a single (prefix, total) butterfly computes both in
⌈log₂p⌉ rounds; otherwise the exscan's last rank completes the total
locally and broadcasts it — either way one schedule, one payload
stream, instead of two back-to-back collectives.

Execution engine (compiled round tables):  the SPMD executor lowers
homogeneous step runs through per-round parameter *tables* instead of
re-deriving everything inside an open-coded Python loop.  Runs whose
rounds share one peer permutation — the segmented ring, whose p−2+S
rounds all ppermute r → r+1 — roll into a SINGLE ``lax.scan`` body
driven by the stacked round parameters (the per-round segment index
``t`` as a ``jnp`` array), so trace size and compile time are O(1) in
p and S rather than O(p+S).  Rounds whose peer offsets vary (doubling
shift chains, butterfly exchanges) must keep one ``ppermute`` trace
site each — XLA's ``ppermute`` takes a *static* permutation — but
those chains are O(log p) rounds by construction, so their traces
stay logarithmic.  The rolled ring is additionally *double-buffered*:
each loop iteration first issues round t's ``ppermute`` and only then
stores round t−1's received segment (carried as the pending
double-buffer), so XLA can overlap the neighbour communication with
the previous round's combine/store work; the final pending store
drains after the loop.  ``SPMDExecutor(unrolled=True)`` keeps the
legacy one-trace-site-per-round ring for the rolled-vs-unrolled
bit-identity law the tests enforce.

⊕ accounting is monoid-aware: for commutative monoids the butterfly
``exchange`` elides the redundant second combine order (2→1 ⊕) and the
fused ``scan_reduce`` round folds the window total once (3→2 ⊕);
``RoundStep.op_count(commutative)`` / ``Schedule.op_count`` expose the
elided counts, the planner prices them, and the executors record
exactly them into :func:`collect_stats`.

Byte prediction note: the plan's ``bytes_on_wire`` for a segmented
schedule is ``rounds · ceil(m/S)``; the traced program zero-pads each
flattened leaf up to a multiple of S, so prediction and measurement
agree exactly when S divides every leaf's element count (the planner
only considers power-of-two S, which also keeps the padding bounded).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import monoid as monoid_lib
from repro.core import oracle


# ---------------------------------------------------------------------------
# Trace/execution-time instrumentation.  Both the SPMD executor (at trace
# time) and the numpy simulator (at execution time) record rounds, ⊕
# applications and all-gathers here, so tests and benchmarks can assert
# the planner's predicted costs on the program that actually runs.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveStats:
    rounds: int = 0  # ppermute calls (communication rounds)
    op_applications: int = 0  # ⊕ applications per device (SPMD lockstep)
    allgathers: int = 0
    bytes_per_round: list = dataclasses.field(default_factory=list)
    # Pallas-path accounting (recorded by PallasExecutor only; the
    # generic/simulator executors leave both at 0).  ``hbm_passes``
    # counts sequential sweeps over a round's payload — kernel
    # launches plus the XLA select sweeps the fused round path
    # absorbs into the kernel; see RoundStep.kernel_passes.
    kernel_launches: int = 0  # pallas_call launches
    hbm_passes: int = 0  # payload HBM traversals of round ⊕ work


_tls = threading.local()


@contextlib.contextmanager
def collect_stats():
    """Context manager capturing round/op counts of scans traced (SPMD)
    or executed (simulator) inside."""
    stats = CollectiveStats()
    prev = getattr(_tls, "stats", None)
    _tls.stats = stats
    try:
        yield stats
    finally:
        _tls.stats = prev


def _stats() -> CollectiveStats | None:
    return getattr(_tls, "stats", None)


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _record_round(tree):
    s = _stats()
    if s is not None:
        s.rounds += 1
        s.bytes_per_round.append(_nbytes(tree))


def _record_op(n: int = 1):
    """Count n ⊕ *executions* (a traced-once loop body records its trip
    count, so stats mean executions, not trace sites)."""
    s = _stats()
    if s is not None:
        s.op_applications += n


def _record_allgather():
    s = _stats()
    if s is not None:
        s.allgathers += 1


def _record_kernel(launches: int, passes: int):
    """Count on-chip kernel launches / HBM passes of one round's ⊕
    work (Pallas executor only; execution counts, like _record_op)."""
    s = _stats()
    if s is not None:
        s.kernel_launches += launches
        s.hbm_passes += passes


# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    """One of S contiguous blocks of the flattened per-rank payload.

    Each leaf is flattened and zero-padded to a multiple of ``count``;
    block ``index`` holds elements [index·k, (index+1)·k) with
    k = ceil(size/count).  ⊕ must combine aligned element blocks
    independently for this to be sound (``Monoid.segmentable``)."""

    index: int
    count: int


@dataclasses.dataclass(frozen=True)
class RoundStep:
    """One round of a schedule.

    kind:
      "shift"       — ppermute r → r+skip; masked receive; combine.
      "seg_shift"   — pipelined-ring round ``t``: neighbour ppermute of
                      one payload segment; rank r stores received
                      segment s = t+1−r (when 0 ≤ s < S) as its result
                      and, if ``prep``, forwards recv ⊕ V[s] next
                      round (1 ⊕).  ``seg`` carries S.
      "exchange"    — butterfly ppermute r ↔ r^skip; two
                      order-preserving combines selected by the rank's
                      side bit.
      "block_exchange" — one round of the block-distributed exscan
                      family (halving/quartering/reduce_scatter): the
                      payload is split into ``seg`` = 2^t rows and the
                      round moves ``rows`` of them (the per-round byte
                      law ``rows · ceil(m/seg)`` the planner prices).
                      ``phase`` narrows the semantics: "fold" pairs off
                      the p mod 2^t surplus ranks, "up" halves the
                      owned row range against virtual partner v^skip
                      (saving both pre-combine halves for the down
                      sweep), "mid" runs a two-⊕ exscan over the
                      2^t-aligned windows on each rank's single owned
                      row, "down" doubles the row range back while
                      turning window prefixes into rank prefixes, and
                      "unfold" returns the folded pairs' results.
                      ``bound`` carries the fold count ρ, ``t`` the
                      phase round index.
      "scan_reduce" — fused exscan+allreduce butterfly round: exchange
                      the window total T with r^skip while the lower
                      side also folds the received total into the
                      exclusive prefix P (3 ⊕ in SPMD lockstep).  After
                      the run P is saved into register ``reg``.
      "allgather"   — XLA-native all-gather of the input V.
      "fold"        — local left-fold of the gathered values below own
                      rank (``fold_count`` ⊕ executions).
      "bcast"       — broadcast rank ``root``'s value (via all-gather).
      "stage"       — control (no round): save W into register ``reg``
                      (if set), rebind the stage input X ← W when
                      ``src == "w"``, then reinit W per ``init``
                      ("identity" | "x" | "w" | a register name).
      "merge"       — control ⊕: W ← W ⊕ reg (reg "$x": the current
                      stage input); W covers the lower ranks.

    axis: mesh axis name this step runs over (None: the executor's
      default axis) — composed multi-axis schedules tag every step.
    send (shift only): "x" the input V, "w" the accumulator,
      "w_op_x" the prepared W ⊕ V (counts one ⊕).
    mask/bound (shift only): receive participation — "ge": r ≥ bound,
      "gt": r > bound.  Non-participants keep W (identity fixup).
    combine (shift only): "copy" W ← recv, or "op" W ← recv ⊕ W (the
      recv side always covers lower ranks — non-commutative safe).
    """

    kind: str
    skip: int = 0
    send: str = "w"
    mask: str = "ge"
    bound: int = 0
    combine: str = "none"
    t: int = -1  # seg_shift round index
    prep: bool = False  # seg_shift: forward-prep ⊕ this round
    fold_count: int = 0  # fold: ⊕ executions
    root: int = 0  # bcast source rank
    axis: Any = None  # mesh axis this step runs over (None: default)
    seg: int = 0  # seg_shift: segment count S of this run
    reg: str = ""  # stage save / merge source / scan_reduce prefix reg
    src: str = ""  # stage: "w" rebinds X ← W
    init: str = "identity"  # stage: new W ("identity"|"x"|"w"|register)
    phase: str = ""  # block_exchange: fold|up|mid|down|unfold
    rows: int = 0  # block_exchange: payload rows this round moves

    @property
    def is_round(self) -> bool:
        """Does this step cost one ppermute communication round?"""
        return self.kind in ("shift", "seg_shift", "exchange",
                             "scan_reduce", "block_exchange")

    @property
    def ops(self) -> int:
        """⊕ executions per device (SPMD lockstep) for this step,
        for a non-commutative monoid (the worst case)."""
        return self.op_count(commutative=False)

    def op_count(self, commutative: bool = False) -> int:
        """⊕ executions per device for this step.

        Commutative monoids elide the redundant combine order: a
        butterfly ``exchange`` computes one combine instead of both
        orders (2→1), and a fused ``scan_reduce`` round folds the
        window total once instead of twice (3→2).  The executors
        apply the same elision, so plans priced off this count match
        :func:`collect_stats` measurements for every monoid."""
        n = 0
        if self.kind == "shift":
            n += 1 if self.send == "w_op_x" else 0
            n += 1 if self.combine == "op" else 0
        elif self.kind == "seg_shift":
            n += 1 if self.prep else 0
        elif self.kind == "exchange":
            n += 1 if commutative else 2
        elif self.kind == "scan_reduce":
            n += 2 if commutative else 3
        elif self.kind == "block_exchange":
            if self.phase in ("fold", "unfold"):
                n += 1  # the folded pair's single combine
            elif self.phase == "up":
                # exchange-shaped: commutative elides the second order
                n += 1 if commutative else 2
            elif self.phase == "mid":
                # copy round carries no ⊕; later rounds prep the send
                # (P ⊕ T) and fold the received window prefix
                n += 0 if self.combine == "copy" else 2
            elif self.phase == "down":
                # lower half preps P ⊕ O_j, upper half adjusts P ⊕ S_j
                # (different operands: no commutative elision)
                n += 2
        elif self.kind == "fold":
            n += self.fold_count
        elif self.kind == "merge":
            n += 1
        return n

    def kernel_passes(self, commutative: bool = False, *,
                      fused: bool = True) -> int:
        """HBM passes over this round's payload on the Pallas path.

        A "pass" is one sequential sweep of the payload: a kernel
        launch, or an XLA select sweep the baseline path runs on a
        kernel's output.  ``fused=True`` is the engine's fused round
        path (one grid pass does the combine orders, the mask/side
        select and the store); ``fused=False`` is the per-round
        ``block_combine`` baseline (one launch per ⊕ plus host-graph
        selects).  Copy/gather rounds carry no ⊕ work and count 0 —
        the metric prices combine traffic, which both modes share
        otherwise.  The fusion wins: ring prep 2→1, non-commutative
        butterfly 3→1, scan_reduce 2→1 (commutative) / 5→1."""
        if self.kind == "shift":
            n = 1 if self.send == "w_op_x" else 0
            return n + (1 if self.combine == "op" else 0)
        if self.kind == "seg_shift":
            if not self.prep:
                return 0
            return 1 if fused else 2  # baseline: combine + valid-select
        if self.kind == "exchange":
            if commutative:
                return 1
            return 1 if fused else 3  # baseline: 2 orders + side select
        if self.kind == "scan_reduce":
            if fused:
                return 1  # (P, T) pair batched into one launch
            return 2 if commutative else 5  # 3 launches + 2 selects
        if self.kind == "block_exchange":
            if self.phase in ("fold", "unfold"):
                # one masked combine; baseline pays the mask select
                return 1 if fused else 2
            if self.phase == "up":
                if commutative:
                    return 1
                return 1 if fused else 3  # 2 orders + side select
            if self.phase == "mid":
                if self.combine == "copy":
                    return 0
                # prep combine + masked window combine (baseline pays
                # the window-mask select on the second)
                return 2 if fused else 3
            # down: two combines plus the side/adjust selects stay in
            # the host graph — no fused down-round kernel, both modes
            # sweep the half-payload four times
            return 4
        if self.kind == "fold":
            return self.fold_count
        if self.kind == "merge":
            return 1
        return 0

    def kernel_launches(self, commutative: bool = False, *,
                        fused: bool = True) -> int:
        """``pallas_call`` launches for this round on the Pallas path
        (per payload dtype group; k same-dtype leaves batch into one
        launch on the fused path)."""
        if self.kind == "shift":
            n = 1 if self.send == "w_op_x" else 0
            return n + (1 if self.combine == "op" else 0)
        if self.kind == "seg_shift":
            return 1 if self.prep else 0
        if self.kind == "exchange":
            return 1 if (commutative or fused) else 2
        if self.kind == "scan_reduce":
            if fused:
                return 1
            return 2 if commutative else 3
        if self.kind == "block_exchange":
            if self.phase in ("fold", "unfold"):
                return 1
            if self.phase == "up":
                return 1 if (commutative or fused) else 2
            if self.phase == "mid":
                return 0 if self.combine == "copy" else 2
            return 2  # down: prep + adjust combines
        if self.kind == "fold":
            return self.fold_count
        if self.kind == "merge":
            return 1
        return 0

    def describe(self) -> str:
        at = f"  @{self.axis}" if self.axis is not None else ""
        if self.kind == "shift":
            send = {"x": "V", "w": "W", "w_op_x": "W⊕V"}[self.send]
            cmp_ = {"ge": ">=", "gt": ">"}[self.mask]
            comb = "W←recv" if self.combine == "copy" else "W←recv⊕W"
            return (f"shift +{self.skip:<4d} send={send:<4s} "
                    f"recv r{cmp_}{self.bound}  {comb}{at}")
        if self.kind == "seg_shift":
            tail = "; send←recv⊕V[s]" if self.prep else "  (drain)"
            return f"ring  t={self.t:<3d} seg s=t+1−r  W[s]←recv{tail}{at}"
        if self.kind == "exchange":
            return f"xchg  r↔r^{self.skip}  W←ordered(recv,W){at}"
        if self.kind == "scan_reduce":
            return (f"scrd  r↔r^{self.skip}  T←ordered(recv,T); "
                    f"low: P←recv⊕P{at}")
        if self.kind == "block_exchange":
            what = {
                "fold": "pair 2i→2i+1: Y←recv⊕V",
                "up": f"v↔v^{self.skip}: keep/swap half rows",
                "mid": ("window copy P←T[w−1]"
                        if self.combine == "copy"
                        else f"w→w+{self.skip}: P←recv⊕P"),
                "down": f"v↔v^{self.skip}: widen P, low sends P⊕O",
                "unfold": "pair 2i+1→2i: return E; odd: P⊕lo",
            }[self.phase]
            return (f"blk   {self.phase:<6s} rows={self.rows}/"
                    f"{self.seg}  {what}{at}")
        if self.kind == "allgather":
            return f"all-gather V{at}"
        if self.kind == "fold":
            return f"local fold of {self.fold_count + 1} gathered values"
        if self.kind == "bcast":
            return f"broadcast rank {self.root} (all-gather){at}"
        if self.kind == "stage":
            save = f" save W→{self.reg!r};" if self.reg else ""
            src = " X←W;" if self.src == "w" else ""
            return f"stage{save}{src} W←{self.init}"
        if self.kind == "merge":
            other = "X" if self.reg == "$x" else repr(self.reg)
            return f"merge W←W⊕{other}"
        return self.kind


@dataclasses.dataclass(frozen=True)
class Schedule:
    """An executable scan program: init state + ordered RoundSteps.

    ``axes`` names the mesh axes (major→minor, with sizes) of a
    composed multi-axis schedule; single-axis schedules leave it empty
    and run over the executor's axis.  ``outputs`` lists what
    ``execute`` returns — "$w" is the final accumulator, anything else
    a register name; more than one entry returns a tuple.  ``layout``
    (set by :func:`fuse`) packs a sequence of payloads into one
    flattened buffer around the run.
    """

    algorithm: str
    kind: str  # "exclusive" | "inclusive" | "allreduce" | "scan_total"
    p: int
    init: str = "identity"  # initial accumulator W: "identity" | "x"
    segments: tuple[Segment, ...] = (Segment(0, 1),)
    steps: tuple[RoundStep, ...] = ()
    axes: tuple = ()  # ((axis_name, size), ...) major→minor; composed
    outputs: tuple = ("$w",)
    layout: "PayloadLayout | None" = None

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def rounds(self) -> int:
        return sum(1 for s in self.steps if s.is_round)

    @property
    def op_applications(self) -> int:
        """⊕ executions for a non-commutative monoid (worst case);
        use :meth:`op_count` for the monoid-aware number."""
        return self.op_count(commutative=False)

    def op_count(self, commutative: bool = False) -> int:
        """⊕ executions per device, honouring the commutative-monoid
        elision in butterfly/scan_reduce rounds."""
        return sum(s.op_count(commutative) for s in self.steps)

    def kernel_passes(self, commutative: bool = False, *,
                      fused: bool = True) -> int:
        """Total HBM passes of the schedule's ⊕ work on the Pallas
        path (see :meth:`RoundStep.kernel_passes`); what
        ``collect_stats().hbm_passes`` measures under the Pallas
        executor in the matching mode."""
        return sum(s.kernel_passes(commutative, fused=fused)
                   for s in self.steps)

    def kernel_launches(self, commutative: bool = False, *,
                        fused: bool = True) -> int:
        """Total ``pallas_call`` launches on the Pallas path."""
        return sum(s.kernel_launches(commutative, fused=fused)
                   for s in self.steps)

    @property
    def allgathers(self) -> int:
        return sum(1 for s in self.steps
                   if s.kind in ("allgather", "bcast"))

    def describe(self) -> str:
        """Round-by-round human-readable listing (no tracing needed)."""
        head = (f"{self.kind} [{self.algorithm}] p={self.p} "
                f"S={self.n_segments} rounds={self.rounds} "
                f"⊕={self.op_applications} "
                f"allgathers={self.allgathers} (W₀={self.init})")
        if self.axes:
            head += " axes=" + "x".join(
                f"{name}:{size}" for name, size in self.axes)
        lines = [head]
        rnd = 0
        for st in self.steps:
            tag = f"r{rnd}" if st.is_round else "--"
            rnd += 1 if st.is_round else 0
            lines.append(f"  {tag:>4s}: {st.describe()}")
        return "\n".join(lines)


def _segs(S: int) -> tuple[Segment, ...]:
    return tuple(Segment(i, S) for i in range(S))


# ---------------------------------------------------------------------------
# Per-round byte laws, priced off the IR.  The planner, the calibration
# features and ``expected_round_bytes`` all read these, so a schedule
# whose rounds move less than the full payload (the segmented ring's
# m/S segments, the block family's row slices) is priced exactly as the
# executors transmit it.
# ---------------------------------------------------------------------------


def step_wire_bytes(st: RoundStep, nbytes: int,
                    default_seg: int = 1) -> int:
    """Bytes one round of ``st`` puts on the wire for an ``nbytes``
    payload: a ceil(m/S) segment per pipelined ring round,
    rows·ceil(m/2^t) for a block-exchange round, the full payload
    otherwise.  Non-round steps move nothing here (all-gathers are
    priced separately, as in ``ScanPlan.bytes_on_wire``)."""
    if not st.is_round:
        return 0
    if st.kind == "seg_shift":
        return -(-nbytes // (st.seg or default_seg))
    if st.kind == "block_exchange":
        return st.rows * -(-nbytes // st.seg)
    return nbytes


def wire_bytes(sched: "Schedule", nbytes: int) -> int:
    """Total round wire bytes of the schedule under the per-round law
    (excluding all-gather traffic)."""
    return sum(step_wire_bytes(st, nbytes, sched.n_segments)
               for st in sched.steps)


def op_wire_bytes(sched: "Schedule", nbytes: int,
                  commutative: bool = False) -> int:
    """⊕-traffic bytes: each step's ⊕ count times the bytes one of its
    ⊕ touches.  For uniform schedules this equals
    ``op_count · ceil(m/S)`` (the legacy planner law); block-exchange
    steps combine only the rows they move."""
    seg = _max_seg(sched)
    total = 0
    for st in sched.steps:
        n = st.op_count(commutative)
        if not n:
            continue
        if st.kind == "block_exchange":
            total += n * st.rows * -(-nbytes // st.seg)
        else:
            total += n * -(-nbytes // seg)
    return total


def pass_wire_bytes(sched: "Schedule", nbytes: int,
                    commutative: bool = False, *,
                    fused: bool = True) -> int:
    """Kernel-pass traffic bytes (the gamma_pass cost-model term):
    each step's HBM passes times the bytes one pass sweeps."""
    seg = _max_seg(sched)
    total = 0
    for st in sched.steps:
        n = st.kernel_passes(commutative, fused=fused)
        if not n:
            continue
        if st.kind == "block_exchange":
            total += n * st.rows * -(-nbytes // st.seg)
        else:
            total += n * -(-nbytes // seg)
    return total


# ---------------------------------------------------------------------------
# Builders: one per registered algorithm.  The planner counts rounds/⊕/
# all-gathers off these schedules, so by construction plans predict what
# the executors measure.
# ---------------------------------------------------------------------------


def build_123(p: int) -> Schedule:
    """Algorithm 1 (123-doubling): skip schedule 1, 2, 3·2^(k−2);
    q = ⌈log₂(p−1)+log₂(4/3)⌉ rounds, q−1 result-path ⊕."""
    steps: list[RoundStep] = []
    if p >= 2:
        steps.append(RoundStep("shift", skip=1, send="x", mask="ge",
                               bound=1, combine="copy"))
    if p >= 3:
        # Round 1 (skip 2): send W ⊕ V (rank 0's W is the identity, so
        # it sends plain V exactly as in the paper); combine iff r >= 2.
        steps.append(RoundStep("shift", skip=2, send="w_op_x", mask="ge",
                               bound=2, combine="op"))
        for s in oracle.skips_123(p)[2:]:
            # rank complete once its window bottoms out (paper: 0 < f)
            steps.append(RoundStep("shift", skip=s, send="w", mask="gt",
                                   bound=s, combine="op"))
    return Schedule("123", "exclusive", p, steps=tuple(steps))


def build_1doubling(p: int) -> Schedule:
    """Shift + straight doubling: 1 + ⌈log₂(p−1)⌉ rounds."""
    steps: list[RoundStep] = []
    if p >= 2:
        steps.append(RoundStep("shift", skip=1, send="x", mask="ge",
                               bound=1, combine="copy"))
        for s in oracle.skips_1doubling(p)[1:]:
            steps.append(RoundStep("shift", skip=s, send="w", mask="gt",
                                   bound=s, combine="op"))
    return Schedule("1doubling", "exclusive", p, steps=tuple(steps))


def build_two_op(p: int) -> Schedule:
    """Two-⊕ doubling: ⌈log₂ p⌉ rounds, two ⊕ per round after the first."""
    steps: list[RoundStep] = []
    if p >= 2:
        steps.append(RoundStep("shift", skip=1, send="x", mask="ge",
                               bound=1, combine="copy"))
        k = 1
        while (1 << k) < p:
            s = 1 << k
            steps.append(RoundStep("shift", skip=s, send="w_op_x",
                                   mask="ge", bound=s, combine="op"))
            k += 1
    return Schedule("two_op", "exclusive", p, steps=tuple(steps))


def build_native(p: int) -> Schedule:
    """Library baseline: all-gather everyone's V, fold locally below own
    rank — zero ppermutes but p·m wire bytes and p−1 local ⊕."""
    steps: tuple[RoundStep, ...] = ()
    if p >= 2:
        steps = (RoundStep("allgather"),
                 RoundStep("fold", fold_count=p - 1))
    return Schedule("native", "exclusive", p, steps=steps)


def build_ring(p: int, segments: int = 1) -> Schedule:
    """Pipelined segmented neighbour ring: p−2+S rounds of one
    m/S-byte segment each (S=1: the plain p−1-round ring).

    Round t: rank r receives segment s = t+1−r (its exclusive prefix
    for that block, complete on arrival) and forwards recv ⊕ V[s] —
    one ⊕ per non-final round, p−3+S total."""
    S = max(1, int(segments))
    if p <= 1:
        return Schedule("ring", "exclusive", p, segments=_segs(S))
    n = p - 2 + S
    steps = tuple(RoundStep("seg_shift", skip=1, t=t, prep=(t < n - 1),
                            seg=S)
                  for t in range(n))
    return Schedule("ring", "exclusive", p, segments=_segs(S),
                    steps=steps)


def _build_block(name: str, p: int, depth: int) -> Schedule:
    """The block-distributed exscan family (vector halving/doubling).

    The payload is split into R = 2^t elementwise rows
    (t = min(depth, ⌊log₂p⌋)) and the scan runs in five phases over
    M = p − ρ *virtual* ranks (ρ = p mod 2^t surplus ranks pair off in
    a fold pre-round and rejoin in an unfold post-round):

      up    — t butterfly rounds halve each rank's owned row range
              against virtual partner v^2^k, so after round k every
              2^(k+1)-rank window's fold is block-distributed over it;
      mid   — a two-⊕ exscan over the M/2^t windows, each rank
              carrying only its single owned row;
      down  — t rounds double the row range back, converting window
              prefixes into per-rank exclusive prefixes: the lower
              sibling sends P ⊕ O_k (its saved pre-combine half), the
              upper adjusts its own rows by the saved received half.

    Round/byte laws (power-of-two p): 2(1−2^−t)·m + (q−t)/2^t·m wire
    bytes over q+t rounds (q = ⌈log₂p⌉) — t=1 ≈ (q+1)/2·m in q+1
    rounds, t=2 ≈ (q+4)/4·m in q+2, t=q ≈ 2(1−1/p)·m in 2q rounds —
    a graded ladder between the doubling schedules (q·m) and the
    segmented ring (→m as S grows).  ρ≠0 adds the fold/unfold round
    pair.  Rows combine elementwise, so these schedules require a
    segmentable monoid (like :func:`segment`)."""
    steps: list[RoundStep] = []
    if p >= 2:
        t = max(1, min(depth, p.bit_length() - 1))
        R = 1 << t
        rho = p % R
        n_w = (p - rho) >> t
        common = dict(seg=R, bound=rho)
        if rho:
            steps.append(RoundStep("block_exchange", phase="fold",
                                   rows=R, skip=1, t=0, **common))
        for k in range(t):
            steps.append(RoundStep("block_exchange", phase="up",
                                   rows=R >> (k + 1), skip=1 << k, t=k,
                                   **common))
        if n_w >= 2:
            steps.append(RoundStep("block_exchange", phase="mid",
                                   rows=1, skip=1, t=0, combine="copy",
                                   **common))
            i = 1
            while (1 << i) < n_w:
                steps.append(RoundStep("block_exchange", phase="mid",
                                       rows=1, skip=1 << i, t=i,
                                       combine="op", **common))
                i += 1
        for j in reversed(range(t)):
            steps.append(RoundStep("block_exchange", phase="down",
                                   rows=R >> (j + 1), skip=1 << j, t=j,
                                   **common))
        if rho:
            steps.append(RoundStep("block_exchange", phase="unfold",
                                   rows=R, skip=1, t=0, **common))
    return Schedule(name, "exclusive", p, steps=tuple(steps))


def build_halving(p: int) -> Schedule:
    """Träff-2026 exclusive scan, depth-1 halving: ⌈log₂p⌉+1 rounds
    (power-of-two p) of ≈(⌈log₂p⌉+1)/2·m total wire bytes."""
    return _build_block("halving", p, 1)


def build_quartering(p: int) -> Schedule:
    """Träff-2026 exclusive scan, depth-2 quartering: ⌈log₂p⌉+2
    rounds (power-of-two p) of ≈(⌈log₂p⌉+4)/4·m total wire bytes."""
    return _build_block("quartering", p, 2)


def build_reduce_scatter(p: int) -> Schedule:
    """Full-depth reduce-scatter (vector halving/doubling) exscan:
    2⌈log₂p⌉ rounds of ≈2·(p−1)/p·m total wire bytes."""
    return _build_block("reduce_scatter", p, max(1, p.bit_length()))


def build_hillis_steele(p: int) -> Schedule:
    """Hillis-Steele inclusive scan: ⌈log₂ p⌉ rounds, one ⊕ each."""
    steps = tuple(RoundStep("shift", skip=s, send="w", mask="ge",
                            bound=s, combine="op")
                  for s in oracle.skips_two_op(p))
    return Schedule("hillis_steele", "inclusive", p, init="x",
                    steps=steps)


def build_butterfly(p: int) -> Schedule:
    """Recursive-doubling all-reduce: ⌈log₂ p⌉ exchange rounds for
    power-of-two p; otherwise inclusive scan + broadcast of the last
    rank (order-preserving for non-commutative monoids)."""
    if p <= 1:
        return Schedule("butterfly", "allreduce", p, init="x")
    if p & (p - 1):  # non-power-of-two
        incl = build_hillis_steele(p)
        steps = incl.steps + (RoundStep("bcast", root=p - 1),)
        return Schedule("butterfly", "allreduce", p, init="x",
                        steps=steps)
    steps = []
    k = 0
    while (1 << k) < p:
        steps.append(RoundStep("exchange", skip=1 << k))
        k += 1
    return Schedule("butterfly", "allreduce", p, init="x",
                    steps=tuple(steps))


def with_total(base: Schedule) -> Schedule:
    """Fuse an allreduce of the input onto an exclusive-scan schedule.

    After the exscan the last rank alone holds the full prefix, so one
    local ⊕ with its own V completes the total, and one broadcast
    distributes it — no second collective sweep.  Returns a
    "scan_total" schedule with ``outputs = (prefix, total)``.
    """
    if base.kind != "exclusive":
        raise ValueError(
            f"with_total composes over exclusive schedules, "
            f"not {base.kind!r}")
    steps = base.steps + (
        RoundStep("stage", reg="prefix", init="w"),
        RoundStep("merge", reg="$x"),
    )
    if base.p >= 2:
        steps = steps + (RoundStep("bcast", root=base.p - 1),)
    return Schedule(f"{base.algorithm}+total", "scan_total", base.p,
                    init=base.init, segments=base.segments, steps=steps,
                    outputs=("prefix", "$w"))


def build_scan_total(p: int) -> Schedule:
    """Fused exscan+allreduce ("scan_total"): for power-of-two p a
    single (prefix, total) butterfly — each round exchanges the window
    total T with r^2^k while the lower side folds the received total
    into its exclusive prefix P — computes BOTH in ⌈log₂ p⌉ rounds,
    the allreduce's round count.

    Non-power-of-two p (where the r^2^k pairing no longer closes)
    reroutes at plan level to an exscan+``with_total`` variant: the
    cheaper, by (rounds, ⊕), of the 123-doubling and two-⊕-doubling
    exscans plus one local ⊕ and a broadcast — the 123 variant wins
    every tie (equal rounds, strictly fewer result-path ⊕), but the
    reroute keeps the choice explicit rather than assumed.
    ``outputs = (prefix, total)``."""
    if p >= 2 and not (p & (p - 1)):
        steps = []
        k = 0
        while (1 << k) < p:
            steps.append(RoundStep("scan_reduce", skip=1 << k,
                                   reg="prefix"))
            k += 1
        return Schedule("fused_doubling", "scan_total", p, init="x",
                        steps=tuple(steps), outputs=("prefix", "$w"))
    sched = min((with_total(build_123(p)), with_total(build_two_op(p))),
                key=lambda s: (s.rounds, s.op_applications))
    return dataclasses.replace(sched, algorithm="fused_doubling")


def segment(schedule: Schedule, S: int) -> Schedule:
    """The segmentation transform: split the payload into S row-blocks
    and stream them through p−2+S neighbour rounds.

    Only schedules made of neighbour rounds (the ring) pipeline this
    way; doubling schedules have data dependencies across non-neighbour
    peers and raise (including their trivially-empty p <= 1 forms)."""
    if schedule.algorithm != "ring" or not all(
            s.kind == "seg_shift" for s in schedule.steps):
        raise ValueError(
            f"only neighbour-ring schedules are segmentable, "
            f"not {schedule.algorithm!r}")
    return build_ring(schedule.p, S)


# ---------------------------------------------------------------------------
# Multi-axis composition (DESIGN §5 as a schedule transform)
# ---------------------------------------------------------------------------


_STAGE_INITS = ("identity", "x", "w")


def _tag_axis(steps, axis):
    """Tag untagged steps with ``axis`` (control steps stay axis-free)."""
    out = []
    for st in steps:
        if st.axis is None and st.kind not in ("stage", "merge"):
            st = dataclasses.replace(st, axis=axis)
        out.append(st)
    return tuple(out)


def _ns_regs(steps, ns: str):
    """Namespace every register reference so inlined sub-schedules
    cannot collide with the composing schedule's own registers."""
    out = []
    for st in steps:
        rep = {}
        if st.reg and st.reg != "$x":
            rep["reg"] = ns + st.reg
        if st.kind == "stage" and st.init not in _STAGE_INITS:
            rep["init"] = ns + st.init
        out.append(dataclasses.replace(st, **rep) if rep else st)
    return tuple(out)


def _ns_outputs(outputs, ns: str):
    return tuple(o if o == "$w" else ns + o for o in outputs)


def _outer_parts(outer: Schedule, outer_axis):
    """Inlineable (steps, axes) of the outer schedule: already-composed
    outers carry their own axis tags; single-axis ones get tagged."""
    steps = _ns_regs(outer.steps, "o:")
    if outer.axes:
        return steps, outer.axes
    if outer_axis is None:
        raise ValueError("outer_axis is required for a single-axis "
                         "outer schedule")
    return _tag_axis(steps, outer_axis), ((outer_axis, outer.p),)


def compose(inner: Schedule, reduce_: Schedule, outer: Schedule, *,
            minor_axis, outer_axis=None) -> Schedule:
    """Inline the DESIGN §5 multi-axis exscan rewrite into ONE schedule.

        exscan(x, (A, B)) = exscan(total_B(x), A) ⊕ exscan(x, B)

    ``inner`` (exclusive) and ``reduce_`` (allreduce) run over the
    minor axis, ``outer`` (exclusive; possibly itself composed) over
    the major axes, stitched by register control steps:  the inner
    prefix is saved, the minor-axis total becomes the outer stage's
    input, and one final ``merge`` applies the combining ⊕.  Every
    step is axis-tagged, so the result lowers/simulates/executes like
    any single-axis schedule.
    """
    if inner.kind != "exclusive" or outer.kind != "exclusive":
        raise ValueError("compose() takes exclusive inner/outer "
                         f"schedules, got {inner.kind!r}/{outer.kind!r}")
    if reduce_.kind != "allreduce":
        raise ValueError(f"compose() needs an allreduce middle "
                         f"schedule, got {reduce_.kind!r}")
    if reduce_.p != inner.p:
        raise ValueError("inner exscan and minor-axis allreduce must "
                         f"share p ({inner.p} != {reduce_.p})")
    o_steps, o_axes = _outer_parts(outer, outer_axis)
    steps = (
        _tag_axis(inner.steps, minor_axis)
        + (RoundStep("stage", reg="inner", init=reduce_.init),)
        + _tag_axis(_ns_regs(reduce_.steps, "r:"), minor_axis)
        + (RoundStep("stage", src="w", init=outer.init),)
        + o_steps
        + (RoundStep("merge", reg="inner"),)
    )
    name = (f"composite({inner.algorithm}+{reduce_.algorithm}"
            f"+{outer.algorithm})")
    return Schedule(name, "exclusive", inner.p * outer.p,
                    init=inner.init, steps=steps,
                    axes=o_axes + ((minor_axis, inner.p),))


def compose_total(inner: Schedule, outer: Schedule, *,
                  minor_axis, outer_axis=None) -> Schedule:
    """Multi-axis "scan_total": the §5 rewrite where the minor-axis
    allreduce IS the inner scan_total's total — no separate reduce
    stage.  Both sub-schedules must be "scan_total" (prefix in
    register ``prefix``, total in W); the result keeps that contract,
    so composition nests for any number of axes."""
    for s, who in ((inner, "inner"), (outer, "outer")):
        if s.kind != "scan_total":
            raise ValueError(f"compose_total needs scan_total "
                             f"sub-schedules; {who} is {s.kind!r}")
    o_steps, o_axes = _outer_parts(outer, outer_axis)
    steps = (
        _tag_axis(_ns_regs(inner.steps, "i:"), minor_axis)
        # W now holds the minor-axis total: it is the outer stage input
        + (RoundStep("stage", src="w", init=outer.init),)
        + o_steps
        # W = grand total; stash it, combine the two partial prefixes,
        # then restore the (prefix in reg, total in W) contract
        + (RoundStep("stage", reg="total", init="o:prefix"),
           RoundStep("merge", reg="i:prefix"),
           RoundStep("stage", reg="prefix", init="total"))
    )
    name = f"composite({inner.algorithm}+{outer.algorithm})"
    return Schedule(name, "scan_total", inner.p * outer.p,
                    init=inner.init, steps=steps,
                    axes=o_axes + ((minor_axis, inner.p),),
                    outputs=("prefix", "$w"))


# ---------------------------------------------------------------------------
# Payload fusion: k concurrent same-kind scans packed into one buffer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PayloadLayout:
    """Packing of k pytree payloads into one flat buffer per leaf slot.

    All payloads share ``treedef``; per leaf slot j the packed buffer
    concatenates every payload's flattened leaf j (``dtypes[j]`` must
    agree across payloads so ⊕ applies uniformly).  ``offsets[i][j]``/
    ``shapes[i][j]`` locate payload i's leaf j inside buffer j;
    ``totals[j]`` is buffer j's element count.  Sound for monoids that
    combine aligned element positions independently
    (``Monoid.segmentable``)."""

    treedef: Any
    dtypes: tuple  # per slot: numpy dtype str, shared by all payloads
    shapes: tuple  # per payload: per slot leaf shape
    offsets: tuple  # per payload: per slot element offset
    totals: tuple  # per slot: total packed elements

    @property
    def n(self) -> int:
        """Number of packed payloads."""
        return len(self.shapes)


def make_layout(xs, *, lead: int = 0) -> PayloadLayout:
    """Build the :class:`PayloadLayout` packing payloads ``xs``
    (``lead`` leading axes — e.g. the simulator's rank axis — are
    excluded from the per-payload shapes)."""
    if not xs:
        raise ValueError("make_layout needs at least one payload")
    _, treedef = jax.tree.flatten(xs[0])
    dtypes = None
    shapes, offsets = [], []
    offs = None
    for x in xs:
        leaves, td = jax.tree.flatten(x)
        if td != treedef:
            raise ValueError(
                f"fused payloads must share one tree structure "
                f"({td} != {treedef})")
        if dtypes is None:
            dtypes = tuple(np.dtype(lf.dtype).str for lf in leaves)
            offs = [0] * len(leaves)
        row_s, row_o = [], []
        for j, lf in enumerate(leaves):
            if np.dtype(lf.dtype).str != dtypes[j]:
                raise ValueError(
                    f"fused payloads must share leaf dtypes; slot {j} "
                    f"has {np.dtype(lf.dtype).str} vs {dtypes[j]}")
            shp = tuple(int(d) for d in lf.shape[lead:])
            row_s.append(shp)
            row_o.append(offs[j])
            offs[j] += int(np.prod(shp, dtype=np.int64))
        shapes.append(tuple(row_s))
        offsets.append(tuple(row_o))
    return PayloadLayout(treedef=treedef, dtypes=dtypes,
                         shapes=tuple(shapes), offsets=tuple(offsets),
                         totals=tuple(offs))


def pack_payloads(layout: PayloadLayout, xs, *, xp=jnp, lead: int = 0):
    """Pack payloads into the layout's flat buffers (one pytree with
    the shared treedef whose leaves are the packed buffers)."""
    flat = [jax.tree.flatten(x)[0] for x in xs]
    if len(flat) != layout.n:
        raise ValueError(f"layout packs {layout.n} payloads, "
                         f"got {len(flat)}")
    bufs = []
    for j in range(len(layout.dtypes)):
        parts = []
        for i in range(layout.n):
            a = xp.asarray(flat[i][j])
            parts.append(a.reshape(a.shape[:lead] + (-1,)))
        bufs.append(xp.concatenate(parts, axis=lead) if len(parts) > 1
                    else parts[0])
    return jax.tree.unflatten(layout.treedef, bufs)


def unpack_payloads(layout: PayloadLayout, packed, *, lead: int = 0):
    """Slice the packed buffers back into the k original payloads."""
    bufs = jax.tree.flatten(packed)[0]
    outs = []
    for i in range(layout.n):
        leaves = []
        for j, buf in enumerate(bufs):
            off = layout.offsets[i][j]
            shp = layout.shapes[i][j]
            size = int(np.prod(shp, dtype=np.int64))
            sl = buf[..., off:off + size]
            leaves.append(sl.reshape(buf.shape[:lead] + shp))
        outs.append(jax.tree.unflatten(layout.treedef, leaves))
    return outs


def fuse(schedules, layout: PayloadLayout) -> Schedule:
    """Fuse k concurrent same-axis/same-kind scans into one schedule:
    the packed payload (per ``layout``) rides the rounds of the
    cheapest compatible schedule, so k scans cost one scan's α·q.

    All schedules must agree on (kind, p, axes) and on their output
    list; executors pack the payload sequence on entry and unpack the
    results on exit — multi-output schedules (scan_total's
    (prefix, total)) unpack to one output tuple per payload."""
    if not schedules:
        raise ValueError("fuse() needs at least one schedule")
    base = min(schedules, key=lambda s: (s.rounds, s.op_applications))
    for s in schedules:
        if (s.kind, s.p, s.axes) != (base.kind, base.p, base.axes):
            raise ValueError(
                "fused schedules must share kind/p/axes; got "
                f"{(s.kind, s.p, s.axes)} vs "
                f"{(base.kind, base.p, base.axes)}")
        if s.outputs != base.outputs:
            raise ValueError(
                "fused schedules must share outputs; got "
                f"{s.outputs} vs {base.outputs}")
        if s.layout is not None:
            raise ValueError("schedule is already fused")
    return dataclasses.replace(
        base, layout=layout,
        algorithm=f"fused[{layout.n}]({base.algorithm})")


def unpack_fused_outputs(layout: PayloadLayout, out, n_outputs: int = 1,
                         *, lead: int = 0):
    """Unpack a fused execution's result back into per-payload results.

    ``n_outputs`` is ``len(schedule.outputs)`` — it cannot be inferred
    from ``out``'s type because tuple-leaf payloads (affine) make a
    single output a tuple too.  Single-output schedules return the
    list of k unpacked payloads; multi-output schedules (scan_total)
    return one tuple per payload — payload i gets
    ``(output0_i, output1_i, ...)``, so a fused scan_total hands every
    request its own (prefix, total)."""
    if n_outputs > 1:
        per_out = [unpack_payloads(layout, o, lead=lead) for o in out]
        return [tuple(po[i] for po in per_out)
                for i in range(layout.n)]
    return unpack_payloads(layout, out, lead=lead)


# ---------------------------------------------------------------------------
# Payload segmentation helpers: each leaf is flattened and split into S
# contiguous element blocks (sound for monoids whose ⊕ combines aligned
# element positions independently — ``Monoid.segmentable``).
# ---------------------------------------------------------------------------


def _jnp_split(a, S: int):
    """Any shape -> (S, ceil(size/S)), flattened and zero-padded."""
    a = jnp.asarray(a).reshape(-1)
    n = a.shape[0]
    k = -(-n // S)
    pad = S * k - n
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
    return a.reshape(S, k)


def _jnp_unsplit(seg, like):
    n = like.size
    return seg.reshape(-1)[:n].reshape(like.shape)


def _np_split(a, S: int):
    a = np.asarray(a).reshape(-1)
    n = a.shape[0]
    k = -(-n // S)
    pad = S * k - n
    if pad:
        a = np.concatenate([a, np.zeros((pad,), a.dtype)])
    return a.reshape(S, k)


def _np_unsplit(seg, like):
    like = np.asarray(like)
    return np.asarray(seg).reshape(-1)[:like.size].reshape(like.shape)


# ---------------------------------------------------------------------------
# Stage-run decomposition shared by the executors: a schedule's steps
# split into control steps (stage/merge) and maximal runs of compute
# steps over one axis (seg_shift and scan_reduce runs kept homogeneous,
# since they carry run-level auxiliary state).
# ---------------------------------------------------------------------------


_STATEFUL = ("seg_shift", "scan_reduce", "block_exchange")


def _step_scope(st: RoundStep, index: int):
    """Trace-time name of one executed step: ``round<index>.<kind>``
    for a communication round (``index`` counts the schedule's rounds
    from 0), the bare kind for local steps (fold, all-gather)."""
    return jax.named_scope(f"round{index}.{st.kind}" if st.is_round
                           else st.kind)


def _stage_runs(steps):
    runs: list = []
    cur: list = []

    def flush():
        nonlocal cur
        if cur:
            runs.append(cur)
            cur = []

    for st in steps:
        if st.kind in ("stage", "merge"):
            flush()
            runs.append(st)
            continue
        if cur and (cur[0].axis != st.axis
                    or (cur[0].kind in _STATEFUL) !=
                    (st.kind in _STATEFUL)
                    or (st.kind in _STATEFUL
                        and cur[0].kind != st.kind)):
            flush()
        cur.append(st)
    flush()
    return runs


def _axis_size(sched: Schedule, axis_tag) -> int:
    if axis_tag is None or not sched.axes:
        return sched.p
    for name, size in sched.axes:
        if name == axis_tag:
            return size
    raise ValueError(
        f"step axis {axis_tag!r} not among schedule axes {sched.axes}")


def _run_seg_count(run, sched: Schedule) -> int:
    return run[0].seg or sched.n_segments


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------


class Executor:
    """One interface, three backends: ``execute(schedule, x, monoid)``.

    ``combine`` is the RoundStep ⊕ hook — subclasses may lower it onto
    different compute substrates (the Pallas executor runs it through
    the on-chip block-combine kernel).  ``masked_combine`` is the fused
    masked form a shift round uses: ONE select on the combine output
    (W ← keep ? lo ⊕ hi : hi) instead of the legacy identity-fixup
    pass + combine + select triple."""

    def combine(self, m: monoid_lib.Monoid, lo, hi):
        """⊕ with ``lo`` covering the lower ranks."""
        return m.op(lo, hi)

    def masked_combine(self, m: monoid_lib.Monoid, keep, lo, hi):
        """Fused masked ⊕: where(keep, lo ⊕ hi, hi), selecting once on
        the combine output.  ``lo`` may be ppermute zero-fill on
        non-kept ranks — the select discards it, so no identity fixup
        pass is needed."""
        combined = self.combine(m, lo, hi)
        return jax.tree.map(
            lambda c, h: jnp.where(keep, c, h), combined, hi)

    def exchange_combine(self, m: monoid_lib.Monoid, recv, w, low_side):
        """One non-commutative butterfly round's update: both combine
        orders, selected by the rank's side bit.  The generic path is
        two ⊕ plus a select sweep; the Pallas engine fuses all three
        into one grid pass."""
        lo = self.combine(m, recv, w)
        hi = self.combine(m, w, recv)
        return jax.tree.map(
            lambda a, b: jnp.where(low_side, a, b), lo, hi)

    def scan_reduce_combine(self, m: monoid_lib.Monoid, recv, w,
                            prefix, low_side):
        """One fused exscan+allreduce round's (T, P) register update.
        Returns (new_w, new_prefix).  The generic path launches one ⊕
        per combine plus selects; the Pallas engine batches the pair
        into a single grid pass."""
        if m.commutative:
            prefix = self.masked_combine(m, low_side, recv, prefix)
            w = self.combine(m, recv, w)
            return w, prefix
        new_p = self.combine(m, recv, prefix)
        t_lo = self.combine(m, recv, w)
        t_hi = self.combine(m, w, recv)
        prefix = jax.tree.map(
            lambda a, b: jnp.where(low_side, a, b), new_p, prefix)
        w = jax.tree.map(
            lambda a, b: jnp.where(low_side, a, b), t_lo, t_hi)
        return w, prefix

    def prep_combine(self, m: monoid_lib.Monoid, valid, recv, seg,
                     ident):
        """The segmented ring's forward-prep ⊕: recv ⊕ V[s] where
        valid, else plain V[s].  Generic path: identity-fixup select
        then combine (two payload sweeps); the Pallas engine runs it
        as one masked-combine pass."""
        base = jax.tree.map(
            lambda t, i: jnp.where(valid, t, i), recv, ident)
        return self.combine(m, base, seg)

    def _note_round_kernels(self, st: "RoundStep",
                            m: monoid_lib.Monoid):
        """Stats hook: executors that lower ⊕ onto on-chip kernels
        record their launch/HBM-pass counts here (no-op otherwise)."""

    def execute(self, schedule: Schedule, x, m: monoid_lib.Monoid):
        raise NotImplementedError


def _ppermute_up(tree, axis_name, skip: int, p: int):
    """The raw ppermute of one shift round (no stats recording):
    rank r sends to r+skip (r+skip < p); non-receiving ranks get
    zero-fill, which callers mask away."""
    perm = [(r, r + skip) for r in range(p - skip)]
    return jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm), tree)


def _vary_like(x, ref):
    """``x`` typed as varying over every mesh axis ``ref`` varies over
    (a no-op outside ``shard_map``'s vma checking), so a loop carry
    seeded with a rank-invariant value matches a varying body output."""
    missing = tuple(jax.typeof(ref).vma - jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _shift_up(tree, axis_name, skip: int, p: int):
    """One communication round: rank r sends to r+skip (r+skip < p).

    Non-receiving ranks get zero-fill from ppermute; callers mask."""
    _record_round(tree)
    return _ppermute_up(tree, axis_name, skip, p)


class SPMDExecutor(Executor):
    """Executes a schedule as the SPMD ppermute program of its rounds.

    Must run where the schedule's axis names are bound (inside
    ``shard_map``); ``axis_name`` is the default for untagged steps.
    Composed multi-axis schedules carry per-step axis tags and run as
    one program.  MPI rank conditionals become the schedule's receive
    masks: a rank with no source "receives" the monoid identity, making
    the combine a no-op (DESIGN.md §2) — implemented as ONE select on
    the combine output (:meth:`Executor.masked_combine`), not a
    separate identity-fixup pass.

    Homogeneous runs execute through compiled round tables: the
    segmented ring's rounds all share the r → r+1 neighbour
    permutation, so the whole run rolls into a single ``lax.scan``
    body over the stacked per-round segment indices — trace size O(1)
    in p and S — with the ring double-buffered (round t's ppermute is
    issued before round t−1's store; see :meth:`_run_segmented`).
    ``unrolled=True`` keeps one trace site per ring round (the legacy
    form) for the rolled-vs-unrolled bit-identity law; varying-offset
    rounds (shift chains, butterfly exchanges) always trace one
    ``ppermute`` site each, as XLA permutations are static."""

    def __init__(self, axis_name=None, *, unrolled: bool = False):
        self.axis_name = axis_name
        self.unrolled = unrolled

    def execute(self, sched: Schedule, x, m: monoid_lib.Monoid):
        if sched.layout is not None:
            packed = pack_payloads(sched.layout, list(x), xp=jnp)
            out = self._execute(sched, packed, m)
            return unpack_fused_outputs(sched.layout, out,
                                        len(sched.outputs))
        return self._execute(sched, x, m)

    def _execute(self, sched: Schedule, x, m: monoid_lib.Monoid):
        regs: dict = {}
        w = x if sched.init == "x" else m.identity_like(x)
        r0 = 0  # rounds before this run: the next round's index
        for run in _stage_runs(sched.steps):
            if isinstance(run, RoundStep):  # control step
                st = run
                if st.kind == "stage":
                    if st.reg:
                        regs[st.reg] = w
                    if st.src == "w":
                        x = w
                    if st.init == "identity":
                        w = m.identity_like(x)
                    elif st.init == "x":
                        w = x
                    elif st.init != "w":
                        w = regs[st.init]
                else:  # merge
                    other = x if st.reg == "$x" else regs[st.reg]
                    w = self.combine(m, w, other)
                    _record_op()
                    self._note_round_kernels(st, m)
                continue
            axis = run[0].axis if run[0].axis is not None \
                else self.axis_name
            p = _axis_size(sched, run[0].axis)
            if run[0].kind == "seg_shift":
                w = self._run_segmented(run, x, m, axis, p,
                                        _run_seg_count(run, sched))
            elif run[0].kind == "scan_reduce":
                w, prefix = self._run_scan_reduce(run, x, w, m, axis, p,
                                                  r0)
                if run[-1].reg:
                    regs[run[-1].reg] = prefix
            elif run[0].kind == "block_exchange":
                w = self._run_block(run, x, m, axis, p)
            else:
                w = self._run_steps(run, x, w, m, axis, p, r0)
            r0 += sum(st.is_round for st in run)
        outs = tuple(w if o == "$w" else regs[o]
                     for o in sched.outputs)
        return outs[0] if len(outs) == 1 else outs

    def _run_steps(self, steps, x, w, m, axis, p, r0):
        r = lax.axis_index(axis)
        gathered = None
        for st in steps:
            with _step_scope(st, r0):
                if st.kind == "shift":
                    if st.send == "x":
                        src = x
                    elif st.send == "w":
                        src = w
                    else:  # "w_op_x": rank 0's W is identity -> sends V
                        src = self.combine(m, w, x)
                        _record_op()
                    recv = _shift_up(src, axis, st.skip, p)
                    has = (r >= st.bound) if st.mask == "ge" else \
                        (r > st.bound)
                    if st.combine == "op":
                        # fused masked combine: one select on the combine
                        # output; ppermute zero-fill on maskless ranks is
                        # discarded by the select, no identity fixup pass
                        w = self.masked_combine(m, has, recv, w)
                        _record_op()
                    else:  # "copy"
                        w = jax.tree.map(
                            lambda c, v: jnp.where(has, c, v), recv, w)
                elif st.kind == "exchange":
                    perm = [(i, i ^ st.skip) for i in range(p)]
                    _record_round(w)
                    recv = jax.tree.map(
                        lambda t: lax.ppermute(t, axis, perm), w)
                    if m.commutative:
                        # both combine orders agree: compute one (2→1 ⊕)
                        w = self.combine(m, recv, w)
                        _record_op()
                    else:
                        low_side = (r & st.skip) != 0  # partner is lower
                        w = self.exchange_combine(m, recv, w, low_side)
                        _record_op(2)
                elif st.kind == "allgather":
                    _record_allgather()
                    gathered = jax.tree.map(
                        lambda t: lax.all_gather(t, axis, axis=0), x)
                elif st.kind == "fold":
                    ident = m.identity_like(x)

                    def body(i, acc):
                        vi = jax.tree.map(lambda g: g[i], gathered)
                        take = i < r
                        combined = self.combine(m, acc, vi)
                        return jax.tree.map(
                            lambda c, a: jnp.where(take, c, a), combined,
                            acc)

                    _record_op(st.fold_count)  # body executes fold_count×
                    w = lax.fori_loop(0, st.fold_count, body, ident)
                elif st.kind == "bcast":
                    _record_allgather()
                    w = jax.tree.map(
                        lambda t: lax.all_gather(t, axis, axis=0)[st.root],
                        w)
                self._note_round_kernels(st, m)
            r0 += st.is_round
        return w

    @jax.named_scope("scan_reduce")
    def _run_scan_reduce(self, steps, x, w, m, axis, p, r0):
        """The fused exscan+allreduce butterfly: W carries the window
        total T (entering as V via init="x"), the auxiliary P the
        exclusive prefix; each round exchanges T with r^skip and the
        lower side folds the received total into P as well.  The
        identity init of P is hoisted out of the round loop; for
        commutative monoids the two T combine orders collapse into
        one (3→2 ⊕ per round)."""
        r = lax.axis_index(axis)
        prefix = m.identity_like(x)  # hoisted: built once per run
        for i, st in enumerate(steps):
            with _step_scope(st, r0 + i):
                perm = [(j, j ^ st.skip) for j in range(p)]
                _record_round(w)
                recv = jax.tree.map(
                    lambda t: lax.ppermute(t, axis, perm), w)
                low_side = (r & st.skip) != 0  # partner covers lower ranks
                w, prefix = self.scan_reduce_combine(m, recv, w, prefix,
                                                     low_side)
                _record_op(2 if m.commutative else 3)
                self._note_round_kernels(st, m)
        return w, prefix

    @jax.named_scope("seg_shift")
    def _run_segmented(self, steps, x, m, axis, p, S):
        """The pipelined ring: stream S leaf row-blocks through
        neighbour rounds; per-rank segment indices are dynamic
        (rank r handles segment t+1−r in round t).

        All rounds share the r → r+1 neighbour permutation, so the run
        compiles to a round table: the per-round segment indices
        ``t`` stack into one array and a single ``lax.scan`` body
        executes every round — trace size O(1) in p and S.  The body
        is double-buffered: round t's ppermute is issued FIRST, then
        round t−1's received segment (the pending buffer in the carry)
        is stored, so XLA overlaps the neighbour communication with
        the previous round's store; the last pending segment drains
        after the loop.  The segment-shaped identity is built once,
        outside the rounds.  ``unrolled=True`` runs the legacy
        one-trace-site-per-round loop instead (bit-identical outputs;
        the property the tests enforce)."""
        r = lax.axis_index(axis)
        V = jax.tree.map(lambda a: _jnp_split(a, S), x)
        R = m.identity_like(V)
        cur = jax.tree.map(lambda a: a[0], V)  # rank 0 sends V[0] first
        # hoisted out of the rounds: ONE segment-shaped identity
        ident = m.identity_like(cur)
        # the loop body below is traced once; stats mean executions
        for st in steps:
            _record_round(cur)
            if st.prep:
                _record_op()
            self._note_round_kernels(st, m)

        def seg_of(tree, slot):
            return jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, slot, 1, 0)[0],
                tree)

        def store(acc, seg, valid, slot):
            old = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, slot, 1, 0), acc)
            upd = jax.tree.map(
                lambda o, c: jnp.where(valid, c[None], o), old, seg)
            return jax.tree.map(
                lambda t, u: lax.dynamic_update_slice_in_dim(
                    t, u, slot, 0), acc, upd)

        def prep(recv, valid, sc):
            # forward Q = recv ⊕ V[s] next round (rank 0: the identity
            # base makes this plain V[t+1], its next raw segment)
            return self.prep_combine(m, valid, recv, seg_of(V, sc),
                                     ident)

        if self.unrolled:
            for st in steps:
                s_recv = st.t + 1 - r
                valid = (r >= 1) & (s_recv >= 0) & (s_recv < S)
                sc = jnp.clip(s_recv, 0, S - 1)
                recv = _ppermute_up(cur, axis, 1, p)
                R = store(R, recv, valid, sc)
                if st.prep:
                    cur = prep(recv, valid, sc)
            return jax.tree.map(_jnp_unsplit, R, x)

        def body(carry, t):
            cur, pend, pvalid, pslot, R = carry
            # round t's communication is issued before round t−1's
            # store — the pending double-buffer XLA overlaps with it
            recv = _ppermute_up(cur, axis, 1, p)
            R = store(R, pend, pvalid, pslot)
            s_recv = t + 1 - r
            valid = (r >= 1) & (s_recv >= 0) & (s_recv < S)
            sc = jnp.clip(s_recv, 0, S - 1)
            cur = prep(recv, valid, sc)
            return (cur, recv, valid, sc, R), None

        # The rolled body preps every iteration; the final (drain)
        # round's prep is dead — its result never leaves the loop —
        # so stats count the IR's p−3+S preps, the result-path ⊕.
        ts = jnp.asarray([st.t for st in steps], dtype=jnp.int32)
        init = (cur, ident, _vary_like(jnp.zeros((), bool), r),
                _vary_like(jnp.zeros((), jnp.int32), r), R)
        (_, pend, pvalid, pslot, R), _ = lax.scan(body, init, ts)
        R = store(R, pend, pvalid, pslot)  # drain the last round
        return jax.tree.map(_jnp_unsplit, R, x)

    @jax.named_scope("block_exchange")
    def _run_block(self, steps, x, m, axis, p):
        """The block-distributed exscan family (see
        :func:`_build_block`).  The payload lives split into R = 2^t
        rows; per-rank row offsets are traced, so each phase round is
        one static ``ppermute`` over the M virtual ranks' physical
        representatives plus static-size dynamic row slices — O(log p)
        trace sites, like the other doubling chains.  Surplus ranks
        (the fold's even partners) idle through the core phases: they
        are in no permutation, and their locally-computed garbage is
        never observed."""
        r = lax.axis_index(axis)
        st0 = steps[0]
        R = st0.seg
        t_eff = R.bit_length() - 1
        rho = st0.bound
        M = p - rho
        reps = [2 * u + 1 if u < rho else u + rho for u in range(M)]
        Y = jax.tree.map(lambda a: _jnp_split(a, R), x)
        v = jnp.where(r < 2 * rho, r // 2, r - rho)
        folded = r < 2 * rho
        odd_folded = folded & (r % 2 == 1)
        even_folded = folded & (r % 2 == 0)
        lo_in = None  # fold: the saved received pair value
        O_saved: dict = {}  # up round k: own pre-combine kept half
        S_saved: dict = {}  # up round k: received partner half
        T = P = None

        def permute(tree, perm):
            return jax.tree.map(
                lambda a: lax.ppermute(a, axis, perm), tree)

        def rows_of(tree, start, n):
            return jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, start, n, 0),
                tree)

        for st in steps:
            if st.phase == "fold":
                _record_round(Y)
                recv = permute(
                    Y, [(2 * i, 2 * i + 1) for i in range(rho)])
                lo_in = recv
                Y = self.masked_combine(m, odd_folded, recv, Y)
            elif st.phase == "up":
                k = st.t
                half = R >> (k + 1)
                bit = (v >> k) & 1
                # the current buffer IS the owned range, so the kept/
                # sent halves are buffer-local: low or high by bit_k(v)
                kept = rows_of(Y, bit * half, half)
                sent = rows_of(Y, (1 - bit) * half, half)
                _record_round(sent)
                recv = permute(
                    sent,
                    [(reps[u], reps[u ^ (1 << k)]) for u in range(M)])
                O_saved[k], S_saved[k] = kept, recv
                if m.commutative:
                    Y = self.combine(m, recv, kept)
                else:
                    # bit set: the partner covers lower virtual ranks
                    Y = self.exchange_combine(m, recv, kept, bit != 0)
            elif st.phase == "mid":
                if T is None:
                    T = Y  # the own-row window fold
                    P = m.identity_like(T)
                w_idx = v >> t_eff
                s = st.skip  # window stride
                d = s << t_eff  # virtual-rank distance
                perm = [(reps[u], reps[u + d]) for u in range(M - d)]
                if st.combine == "copy":
                    _record_round(T)
                    recv = permute(T, perm)
                    P = jax.tree.map(
                        lambda c, h: jnp.where(w_idx >= s, c, h),
                        recv, P)
                else:
                    # window 0's P is the identity, so it sends plain T
                    send = self.combine(m, P, T)
                    _record_round(send)
                    recv = permute(send, perm)
                    P = self.masked_combine(m, w_idx >= s, recv, P)
            elif st.phase == "down":
                j = st.t
                half = R >> (j + 1)
                if P is None:  # single window: no mid rounds ran
                    P = m.identity_like(Y)
                bit = (v >> j) & 1
                lower = bit == 0
                prepped = self.combine(m, P, O_saved[j])
                send = jax.tree.map(
                    lambda a, b: jnp.where(lower, a, b), prepped, P)
                _record_round(send)
                recv = permute(
                    send,
                    [(reps[u], reps[u ^ (1 << j)]) for u in range(M)])
                adj = self.combine(m, P, S_saved[j])
                own = jax.tree.map(
                    lambda pp, a: jnp.where(lower, pp, a), P, adj)
                # widen: own rows keep their side of the doubled
                # range, the received sibling rows fill the other
                P = jax.tree.map(
                    lambda o, c: jnp.where(
                        lower,
                        jnp.concatenate([o, c], axis=0),
                        jnp.concatenate([c, o], axis=0)), own, recv)
            else:  # unfold
                _record_round(P)
                recv = permute(
                    P, [(2 * i + 1, 2 * i) for i in range(rho)])
                adj = self.combine(m, P, lo_in)
                P = jax.tree.map(
                    lambda a, c, pp: jnp.where(
                        odd_folded, a,
                        jnp.where(even_folded, c, pp)), adj, recv, P)
            _record_op(st.op_count(m.commutative))
            self._note_round_kernels(st, m)
        if P is None:  # p == 1: no steps at all, but guard anyway
            P = Y
        return jax.tree.map(_jnp_unsplit, P, x)


class PallasExecutor(SPMDExecutor):
    """SPMD executor whose RoundStep ⊕ hooks run on-chip through the
    single-pass scan engine (``kernels.scan_engine``, DESIGN §7):
    elementwise monoids (``Monoid.leaf_op``) and the affine pair are
    tiled through VMEM; other structured monoids (matmul) fall back to
    the plain op.

    ``fused=True`` (default) is the engine's fused round path: a
    round's combine order(s), its receive-mask/side select, and the
    result store run in ONE grid pass, with a round's same-dtype
    payload leaves (fused-layout slots, scan_reduce's (P, T) pair)
    batched into a single ``pallas_call``.  ``fused=False`` keeps the
    legacy per-round per-leaf ``block_combine`` launches with
    host-graph selects — the baseline ``benchmarks/exec_bench.py``
    measures the fusion against.  Either mode records its kernel
    launch / HBM-pass counts into :func:`collect_stats`
    (``kernel_launches`` / ``hbm_passes``), matching
    :meth:`Schedule.kernel_passes` by construction.

    Note: ``shard_map`` has no replication rule for ``pallas_call`` —
    wrap the call site with ``check_vma=False``."""

    def __init__(self, axis_name=None, *, interpret: bool | None = None,
                 block_rows: int = 256, fused: bool = True):
        super().__init__(axis_name)
        self.interpret = interpret
        self.block_rows = block_rows
        self.fused = fused

    def _interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return self.interpret

    def _engine(self):
        from repro.kernels import scan_engine
        return scan_engine

    def combine(self, m: monoid_lib.Monoid, lo, hi):
        se = self._engine()
        if self.fused:
            out = se.tree_combine(m, lo, hi,
                                  block_rows=self.block_rows,
                                  interpret=self._interpret())
            if out is not None:
                return out
        elif m.leaf_op is not None:
            interpret = self._interpret()
            return jax.tree.map(
                lambda a, b: se.block_combine(
                    a, b, m.leaf_op, block_rows=self.block_rows,
                    interpret=interpret), lo, hi)
        return super().combine(m, lo, hi)

    def masked_combine(self, m: monoid_lib.Monoid, keep, lo, hi):
        """The fused masked path: select(keep, a ⊕ b, b) in ONE pass
        through VMEM (the kernel's ``keep`` operand), instead of a
        combine kernel launch followed by a host-graph select."""
        se = self._engine()
        if self.fused:
            out = se.tree_combine(m, lo, hi, keep=keep,
                                  block_rows=self.block_rows,
                                  interpret=self._interpret())
            if out is not None:
                return out
        elif m.leaf_op is not None:
            interpret = self._interpret()
            return jax.tree.map(
                lambda a, b: se.block_combine(
                    a, b, m.leaf_op, keep=keep,
                    block_rows=self.block_rows, interpret=interpret),
                lo, hi)
        return super().masked_combine(m, keep, lo, hi)

    def exchange_combine(self, m: monoid_lib.Monoid, recv, w, low_side):
        if self.fused:
            out = self._engine().tree_exchange(
                m, recv, w, low_side, block_rows=self.block_rows,
                interpret=self._interpret())
            if out is not None:
                return out
        return super().exchange_combine(m, recv, w, low_side)

    def scan_reduce_combine(self, m: monoid_lib.Monoid, recv, w,
                            prefix, low_side):
        if self.fused:
            out = self._engine().tree_scan_reduce(
                m, recv, w, prefix, low_side,
                block_rows=self.block_rows,
                interpret=self._interpret())
            if out is not None:
                return out
        return super().scan_reduce_combine(m, recv, w, prefix,
                                           low_side)

    def prep_combine(self, m: monoid_lib.Monoid, valid, recv, seg,
                     ident):
        if self.fused:
            # one masked-combine pass: valid ? recv ⊕ V[s] : V[s]
            # (identity absorption folds the fixup select away)
            return self.masked_combine(m, valid, recv, seg)
        return super().prep_combine(m, valid, recv, seg, ident)

    def _note_round_kernels(self, st: RoundStep, m: monoid_lib.Monoid):
        if not self._engine().supports(m):
            return  # plain-XLA fallback: no kernel accounting
        _record_kernel(
            st.kernel_launches(m.commutative, fused=self.fused),
            st.kernel_passes(m.commutative, fused=self.fused))


class SimulatorExecutor(Executor):
    """Pure-numpy rank-by-rank execution of a schedule at any p — no
    devices, no tracing.  Leaves carry a leading rank axis of size p
    (row-major over the schedule's axes for composed multi-axis
    schedules: each run's rounds act within independent axis groups,
    exactly like MPI communicator splits).

    Records the same aggregate stats as the SPMD executor into the
    ambient :func:`collect_stats` context, so plan-vs-execution drift is
    checkable host-side (dry-run, benchmark ``--check`` modes)."""

    def execute(self, sched: Schedule, x, m: monoid_lib.Monoid):
        op = monoid_lib.NUMPY_OPS.get(m.name, m.op)
        ident_fn = monoid_lib.NUMPY_IDENTITY.get(m.name)
        if ident_fn is None:
            def ident_fn(t):
                return jax.tree.map(np.asarray, m.identity_like(t))

        if sched.layout is not None:
            xs = [jax.tree.map(np.asarray, xi) for xi in x]
            packed = pack_payloads(sched.layout, xs, xp=np, lead=1)
            out = self._execute(sched, packed, m, op, ident_fn)
            return unpack_fused_outputs(sched.layout, out,
                                        len(sched.outputs), lead=1)
        return self._execute(sched, x, m, op, ident_fn)

    def _execute(self, sched, x, m, op, ident_fn):
        p = sched.p
        if p == 0:
            return x
        X = [jax.tree.map(lambda a: np.asarray(a)[q], x)
             for q in range(p)]
        if sched.init == "x":
            W = [jax.tree.map(np.copy, v) for v in X]
        else:
            W = [ident_fn(v) for v in X]
        regs: dict = {}
        for run in _stage_runs(sched.steps):
            if isinstance(run, RoundStep):  # control step
                st = run
                if st.kind == "stage":
                    if st.reg:
                        regs[st.reg] = list(W)
                    if st.src == "w":
                        X = list(W)
                    if st.init == "identity":
                        W = [ident_fn(v) for v in X]
                    elif st.init == "x":
                        W = [jax.tree.map(np.copy, v) for v in X]
                    elif st.init != "w":
                        W = list(regs[st.init])
                else:  # merge
                    other = X if st.reg == "$x" else regs[st.reg]
                    _record_op()
                    W = [op(W[q], other[q]) for q in range(p)]
                continue
            groups = _axis_groups(sched, run[0].axis)
            if run[0].kind == "seg_shift":
                self._run_segmented(run, X, W, op, ident_fn, groups,
                                    _run_seg_count(run, sched))
            elif run[0].kind == "block_exchange":
                self._run_block(run, X, W, op, ident_fn, groups,
                                m.commutative)
            elif run[0].kind == "scan_reduce":
                prefix = self._run_scan_reduce(run, X, W, op, ident_fn,
                                               groups, m.commutative)
                if run[-1].reg:
                    regs[run[-1].reg] = prefix
            else:
                self._run_steps(run, X, W, op, ident_fn, groups,
                                m.commutative)
        outs = []
        for o in sched.outputs:
            vals = W if o == "$w" else regs[o]
            outs.append(jax.tree.map(
                lambda *ws: np.stack(ws, axis=0), *vals))
        return outs[0] if len(outs) == 1 else tuple(outs)

    def _run_steps(self, steps, X, W, op, ident_fn, groups,
                   commutative=False):
        gathered: dict = {}
        for st in steps:
            if st.kind == "shift":
                recorded = False
                for g in groups:
                    pg = len(g)
                    if st.send == "x":
                        payload = [X[i] for i in g]
                    elif st.send == "w":
                        payload = [W[i] for i in g]
                    else:
                        payload = [op(W[i], X[i]) for i in g]
                    if not recorded:
                        if st.send == "w_op_x":
                            _record_op()
                        _record_round(payload[0])
                        if st.combine == "op":
                            _record_op()
                        recorded = True
                    ok = (lambda q: q >= st.bound) if st.mask == "ge" \
                        else (lambda q: q > st.bound)
                    old = [W[i] for i in g]
                    for q in range(st.skip, pg):
                        if ok(q):
                            recv = payload[q - st.skip]
                            W[g[q]] = recv if st.combine == "copy" \
                                else op(recv, old[q])
            elif st.kind == "exchange":
                _record_round(W[groups[0][0]])
                _record_op(st.op_count(commutative))
                for g in groups:
                    old = [W[i] for i in g]
                    for q, i in enumerate(g):
                        j = q ^ st.skip
                        # commutative monoids compute one combine
                        # order (2→1 ⊕ in SPMD lockstep); order here
                        # matches the SPMD executor bit-for-bit
                        W[i] = op(old[j], old[q]) if (
                            commutative or q & st.skip) \
                            else op(old[q], old[j])
            elif st.kind == "allgather":
                _record_allgather()
                for gi, g in enumerate(groups):
                    gathered[gi] = [X[i] for i in g]
            elif st.kind == "fold":
                _record_op(st.fold_count)
                for gi, g in enumerate(groups):
                    got = gathered[gi]
                    for q, i in enumerate(g):
                        acc = ident_fn(X[i])
                        for t in range(q):
                            acc = op(acc, got[t])
                        W[i] = acc
            elif st.kind == "bcast":
                _record_allgather()
                for g in groups:
                    root_val = W[g[st.root]]
                    for i in g:
                        W[i] = root_val

    def _run_scan_reduce(self, steps, X, W, op, ident_fn, groups,
                         commutative=False):
        prefix = [ident_fn(v) for v in X]
        for st in steps:
            _record_round(W[groups[0][0]])
            _record_op(st.op_count(commutative))
            for g in groups:
                old = [W[i] for i in g]
                for q, i in enumerate(g):
                    j = q ^ st.skip
                    if q & st.skip:  # partner covers lower ranks
                        prefix[i] = op(old[j], prefix[i])
                        W[i] = op(old[j], old[q])
                    else:
                        # commutative: one combine order (3→2 ⊕)
                        W[i] = op(old[j], old[q]) if commutative \
                            else op(old[q], old[j])
        return prefix

    def _run_segmented(self, steps, X, W, op, ident_fn, groups, S):
        state = []
        seg_of = (lambda v, s: jax.tree.map(lambda a: a[s], v))
        for g in groups:
            Vs = [jax.tree.map(lambda a: _np_split(a, S), X[i])
                  for i in g]
            R = [ident_fn(v) for v in Vs]
            cur = [jax.tree.map(lambda a: a[0].copy(), v) for v in Vs]
            # hoisted out of the rounds: one segment-shaped identity
            # per rank (was rebuilt every round for pre-window ranks)
            idents = [ident_fn(seg_of(v, 0)) for v in Vs]
            state.append((Vs, R, cur, idents))
        for st in steps:
            _record_round(state[0][2][0])
            if st.prep:
                _record_op()
            for gi, g in enumerate(groups):
                Vs, R, cur, idents = state[gi]
                pg = len(g)
                recv = [None] + cur[:-1]  # neighbour shift r-1 -> r
                ncur = list(cur)
                for q in range(pg):
                    s = st.t + 1 - q
                    valid = q >= 1 and 0 <= s < S
                    sc = min(max(s, 0), S - 1)
                    base = recv[q] if valid else idents[q]
                    if valid:
                        R[q] = jax.tree.map(
                            lambda acc, b: _np_set_seg(acc, sc, b),
                            R[q], base)
                    if st.prep:
                        ncur[q] = op(base, seg_of(Vs[q], sc))
                state[gi] = (Vs, R, ncur, idents)
        for gi, g in enumerate(groups):
            Vs, R, _, _ = state[gi]
            for q, i in enumerate(g):
                W[i] = jax.tree.map(_np_unsplit, R[q],
                                    jax.tree.map(np.asarray, X[i]))

    def _run_block(self, steps, X, W, op, ident_fn, groups,
                   commutative=False):
        """Rank-by-rank twin of ``SPMDExecutor._run_block``: state is
        kept per *virtual* rank (the fold's even partners idle through
        the core phases), combine orders match the SPMD executor
        bit-for-bit, and each step records one representative
        transmitted tree — ``rows`` rows of the split payload, the
        IR's byte law."""
        st0 = steps[0]
        R = st0.seg
        t_eff = R.bit_length() - 1
        rho = st0.bound
        pg = len(groups[0])
        M = pg - rho
        reps = [2 * u + 1 if u < rho else u + rho for u in range(M)]
        sl = (lambda tree, a, n:
              jax.tree.map(lambda x_: x_[a:a + n], tree))
        state = []
        for g in groups:
            Vs = [jax.tree.map(lambda a: _np_split(a, R), X[i])
                  for i in g]
            state.append({
                "Vs": Vs, "lo": [None] * pg,
                "Y": [jax.tree.map(np.copy, Vs[reps[u]])
                      for u in range(M)],
                "O": {}, "S": {},
                "T": None, "P": None, "even": None,
            })
        for st in steps:
            _record_round(jax.tree.map(lambda a: a[:st.rows],
                                       state[0]["Vs"][0]))
            _record_op(st.op_count(commutative))
            for s_ in state:
                Vs, Y = s_["Vs"], s_["Y"]
                if st.phase == "fold":
                    for u in range(rho):
                        s_["lo"][2 * u + 1] = Vs[2 * u]
                        Y[u] = op(Vs[2 * u], Y[u])
                elif st.phase == "up":
                    k = st.t
                    half = R >> (k + 1)
                    kept, sent = [], []
                    for u in range(M):
                        bit = (u >> k) & 1
                        # buffer-local halves: the current buffer IS
                        # the owned row range
                        kept.append(sl(Y[u], bit * half, half))
                        sent.append(sl(Y[u], (1 - bit) * half, half))
                    recvs = [sent[u ^ (1 << k)] for u in range(M)]
                    s_["O"][k], s_["S"][k] = kept, recvs
                    for u in range(M):
                        bit = (u >> k) & 1
                        Y[u] = op(recvs[u], kept[u]) \
                            if (commutative or bit) \
                            else op(kept[u], recvs[u])
                elif st.phase == "mid":
                    if s_["T"] is None:
                        s_["T"] = list(Y)
                        s_["P"] = [ident_fn(y) for y in Y]
                    T, P = s_["T"], s_["P"]
                    s = st.skip
                    d = s << t_eff
                    if st.combine == "copy":
                        send = T
                    else:
                        send = [op(P[u], T[u]) for u in range(M)]
                    s_["P"] = [
                        (send[u - d] if st.combine == "copy"
                         else op(send[u - d], P[u]))
                        if (u >> t_eff) >= s else P[u]
                        for u in range(M)]
                elif st.phase == "down":
                    j = st.t
                    if s_["P"] is None:  # single window: no mid ran
                        s_["P"] = [ident_fn(y) for y in Y]
                    P, O, S2 = s_["P"], s_["O"][j], s_["S"][j]
                    send = [P[u] if (u >> j) & 1
                            else op(P[u], O[u]) for u in range(M)]
                    newP = []
                    for u in range(M):
                        bit = (u >> j) & 1
                        recv = send[u ^ (1 << j)]
                        own = op(P[u], S2[u]) if bit else P[u]
                        a, b = (own, recv) if bit == 0 \
                            else (recv, own)
                        newP.append(jax.tree.map(
                            lambda x_, y_: np.concatenate(
                                [x_, y_], axis=0), a, b))
                    s_["P"] = newP
                else:  # unfold
                    P = s_["P"]
                    # even partners get the pre-adjust prefix (copy)
                    s_["even"] = [P[u] for u in range(rho)]
                    for u in range(rho):
                        P[u] = op(P[u], s_["lo"][2 * u + 1])
        for gi, g in enumerate(groups):
            s_ = state[gi]
            for u in range(M):
                i = g[reps[u]]
                W[i] = jax.tree.map(
                    _np_unsplit, s_["P"][u],
                    jax.tree.map(np.asarray, X[i]))
            for u in range(rho):
                i = g[2 * u]
                W[i] = jax.tree.map(
                    _np_unsplit, s_["even"][u],
                    jax.tree.map(np.asarray, X[i]))


def _axis_groups(sched: Schedule, axis_tag):
    """Independent rank groups of one axis of a (possibly composed)
    schedule: flat ranks are row-major over ``sched.axes``; a step over
    axis j acts within each group obtained by fixing every other
    coordinate — the simulator twin of a named-axis collective."""
    p = sched.p
    if axis_tag is None or not sched.axes:
        return [list(range(p))]
    names = [name for name, _ in sched.axes]
    sizes = [size for _, size in sched.axes]
    j = names.index(axis_tag)
    strides = [1] * len(sizes)
    for i in range(len(sizes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]
    others = [range(s) for i, s in enumerate(sizes) if i != j]
    groups = []
    for combo in itertools.product(*others):
        coords = list(combo)
        coords.insert(j, 0)
        base = sum(c * strides[i] for i, c in enumerate(coords))
        groups.append([base + k * strides[j] for k in range(sizes[j])])
    return groups


def _np_set_seg(acc, s: int, value):
    acc = np.asarray(acc).copy()
    acc[s] = value
    return acc


# ---------------------------------------------------------------------------
# Trace-size accounting (the compiled-round-table win, measurable)
# ---------------------------------------------------------------------------


def jaxpr_eqn_count(jaxpr) -> int:
    """Total equation count of a (closed) jaxpr, including nested
    sub-jaxprs (a rolled ``lax.scan`` body counts once — the honest
    metric for the round-table trace-size win; an unrolled ring pays
    its body once per round)."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    n = 0
    for eq in jaxpr.eqns:
        n += 1
        for v in eq.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                if hasattr(sub, "jaxpr") or hasattr(sub, "eqns"):
                    n += jaxpr_eqn_count(sub)
    return n


def trace_eqn_count(sched: Schedule, m: monoid_lib.Monoid, x, *,
                    axis_name="x", mesh=None,
                    unrolled: bool = False) -> int:
    """Equation count of the schedule's traced SPMD program (no
    compilation, no execution — ``jax.make_jaxpr`` under
    ``shard_map``).  ``x`` carries a leading rank axis of size p;
    requires a mesh (or enough devices to build one) spanning p."""
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        devs = jax.devices()
        if len(devs) < sched.p:
            raise RuntimeError(
                f"tracing a p={sched.p} schedule needs {sched.p} "
                f"devices, have {len(devs)}")
        mesh = Mesh(np.array(devs[:sched.p]).reshape(sched.p),
                    (axis_name,))
    ex = SPMDExecutor(axis_name, unrolled=unrolled)
    specs = jax.tree.map(lambda _: P(axis_name), x)
    fn = shard_map(lambda v: ex.execute(sched, v, m), mesh=mesh,
                   in_specs=(specs,), out_specs=specs)
    return jaxpr_eqn_count(jax.make_jaxpr(fn)(x))


# ---------------------------------------------------------------------------
# Host-side plan verification (dry-run / benchmark drift checks)
# ---------------------------------------------------------------------------


def _witness_payload(name: str, p: int, n0: int, seed: int):
    rng = np.random.default_rng(seed)
    if name == "affine":
        return (rng.standard_normal((p, n0)),
                rng.standard_normal((p, n0)))
    if name == "matmul":
        return rng.standard_normal((p, 4, 4)) * 0.5
    if name in ("add", "xor"):
        return rng.integers(0, 1 << 30, size=(p, n0)).astype(np.int64)
    return rng.standard_normal((p, n0))


def _host_reference(kind: str, x, op, ident_fn, p: int):
    V = [jax.tree.map(lambda a: np.asarray(a)[q], x) for q in range(p)]
    if kind == "scan_total":
        return (_host_reference("exclusive", x, op, ident_fn, p),
                _host_reference("allreduce", x, op, ident_fn, p))
    out = []
    if kind == "exclusive":
        acc = ident_fn(V[0])
        for q in range(p):
            out.append(acc)
            acc = op(acc, V[q])
    elif kind == "inclusive":
        acc = ident_fn(V[0])
        for q in range(p):
            acc = op(acc, V[q])
            out.append(acc)
    else:  # allreduce
        acc = ident_fn(V[0])
        for q in range(p):
            acc = op(acc, V[q])
        out = [acc] * p
    return jax.tree.map(lambda *ws: np.stack(ws, axis=0), *out)


def _max_seg(sched: Schedule) -> int:
    return max((st.seg or sched.n_segments for st in sched.steps
                if st.kind == "seg_shift"), default=1)


def expected_round_bytes(sched: Schedule, per_rank) -> int:
    """The schedule's per-round byte law summed over its rounds: one
    m/S-byte segment per pipelined ring round, the full payload per
    shift/exchange/scan_reduce round (all-gathers are accounted
    separately, as in ``ScanPlan.bytes_on_wire``)."""
    leaves = [np.asarray(t) for t in jax.tree.leaves(per_rank)]
    total = 0
    for st in sched.steps:
        if not st.is_round:
            continue
        if st.kind == "seg_shift":
            S = st.seg or sched.n_segments
            total += sum(-(-t.size // S) * t.dtype.itemsize
                         for t in leaves)
        elif st.kind == "block_exchange":
            total += sum(st.rows * -(-t.size // st.seg)
                         * t.dtype.itemsize for t in leaves)
        else:
            total += sum(t.size * t.dtype.itemsize for t in leaves)
    return total


def verify_plan(plan, *, rank_elems: int = 2, seed: int = 0) -> dict:
    """Execute ``plan``'s schedule in the numpy simulator against a
    sequential host reference; returns measured-vs-predicted stats.

    Since the composition refactor every plan — single-axis,
    multi-axis (composed into one axis-annotated schedule) and
    scan_total — verifies through the same path.  Used by the dry-run
    (every cell's resolved scan plans) and the benchmark ``--check``
    smoke modes so plan/measurement drift fails fast, without devices.
    """
    m = monoid_lib.get(plan.spec.monoid)
    op = monoid_lib.NUMPY_OPS.get(m.name, m.op)
    ident_fn = monoid_lib.NUMPY_IDENTITY.get(
        m.name, lambda t: jax.tree.map(np.asarray, m.identity_like(t)))
    sched = plan.schedule()
    S = max(_max_seg(sched), 1)
    n0 = S * rank_elems
    x = _witness_payload(m.name, plan.p, n0, seed)
    with collect_stats() as st:
        got = SimulatorExecutor().execute(sched, x, m)
    want = _host_reference(plan.spec.kind, x, op, ident_fn, plan.p)
    close = all(
        np.allclose(g, w, rtol=1e-10, atol=1e-12)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    # byte accounting: the witness is built with S | element count, so
    # the schedule's per-round law must match measurement exactly
    per_rank = jax.tree.map(lambda a: np.asarray(a)[0], x)
    bytes_expected = expected_round_bytes(sched, per_rank)
    res = {
        "algorithm": plan.algorithm, "p": plan.p,
        "segments": plan.segments,
        "rounds_predicted": plan.rounds, "rounds_measured": st.rounds,
        "ops_predicted": plan.op_applications,
        "ops_measured": st.op_applications,
        "allgathers_predicted": plan.allgathers,
        "allgathers_measured": st.allgathers,
        "bytes_expected": bytes_expected,
        "bytes_measured": sum(st.bytes_per_round),
        "correct": bool(close),
    }
    res["ok"] = bool(
        close
        and st.rounds == plan.rounds
        and st.op_applications == plan.op_applications
        and st.allgathers == plan.allgathers
        and sum(st.bytes_per_round) == bytes_expected)
    return res
