"""Tracing, named scopes, and the communication/computation cost model.

TPU-native re-imagining of the reference's critter profiling integration
(SURVEY §5.1).  The reference compile-gates symbol macros around functions and
algorithm phases (``CRITTER_START(CI::trsm)`` etc., cholinv.hpp:94-136,
cacqr.hpp:82-116) and the external critter library measures per-symbol
computation/communication costs along the critical path, per process, and
volumetrically (autotune/cholesky/cholinv/tune.cpp:28-88).

On TPU the execution model is different: everything inside ``jit`` is compiled
into one XLA program, so per-phase *measurement* from Python is impossible —
the phases fuse.  The equivalent design here has three parts:

1. **Named scopes** (`scope`): phase tags (the same names the reference uses —
   ``CI::trsm``, ``CI::tmu``, ``CQR::gram``...) entered as `jax.named_scope`,
   so every HLO op carries its phase in metadata and `jax.profiler` traces
   decompose by phase in Perfetto/TensorBoard exactly like critter's symbol
   decomposition.  Each Pallas kernel is named ``<phase>.<kernel>``
   (`kernel_name`), so the trace calls it by that name too.

2. **An analytic cost model** (`Recorder` + ``*_cost``): at trace time, the
   SUMMA layer and the algorithm base cases emit per-phase flop counts,
   collective byte counts, and collective (synchronization) counts computed
   from shapes and the grid — the alpha-beta model critter fits empirically
   (cp-comp / cp-comm / cp-synch columns), derived analytically instead.
   Tracing happens once per jit cache entry, so a Recorder activated around
   the *first* call of a jitted function captures exactly one execution's
   worth of costs.

3. **Cost tables** (`write_times_table` / `write_costs_table`): fixed-width
   text tables in the shape of the reference's autotune output
   (autotune/util.h:4-127), consumed by capital_tpu/autotune.

Device constants (`DeviceSpec`) are public-spec estimates used to convert the
model's flops/bytes into seconds for the table's time columns; measured time
comes from the device trace (benchmark/trace_reduce.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import defaultdict
from typing import Optional

import jax
import jax.numpy as jnp

from capital_tpu.obs import spans

# JAX's build events become program spans from here on (obs/spans.py)
spans.watch_builds()

# --------------------------------------------------------------------------
# device specs (public numbers; estimates for the model's time conversion)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-chip hardware model: peak matmul throughput + interconnect/memory
    bandwidth + HBM capacity.  The analog of the alpha-beta machine
    parameters critter fits.

    ``alpha_s`` is the per-collective launch/synchronization latency — the
    alpha of the alpha-beta model (CA-CQR2's S term, arXiv:1710.08471 §2).
    Public ICI latencies sit around a microsecond; the CPU rig's in-process
    ring is priced the same order (it only matters for relative ranking
    there)."""

    name: str
    peak_bf16_tflops: float
    hbm_gbps: float
    ici_gbps: float  # chip-to-chip interconnect bandwidth per chip, GB/s
    hbm_bytes: float
    alpha_s: float = 1e-6  # per-collective latency (seconds)

    def peak_tflops(self, dtype) -> float:
        if jnp.dtype(dtype).itemsize >= 4:
            return self.peak_bf16_tflops / 2.0  # f32 via 2-pass bf16 (bound)
        return self.peak_bf16_tflops


_V5E = DeviceSpec("v5e", 197.0, 819.0, 200.0, 16e9)
_V5P = DeviceSpec("v5p", 459.0, 2765.0, 600.0, 95e9)
_V6E = DeviceSpec("v6e", 918.0, 1640.0, 448.0, 32e9)

#: THE peak table, keyed by jax ``device_kind`` (both spellings jax's pallas
#: tpu_info knows).  Source: Google Cloud TPU documentation, system
#: architecture pages "TPU v5e", "TPU v5p", "TPU v6e" — peak bf16 compute
#: per chip, HBM capacity and bandwidth, inter-chip interconnect bandwidth
#: (v5e 1,600 Gbit/s = 200 GB/s, v5p 4,800 Gbit/s, v6e 3,584 Gbit/s).
#: ``cpu`` is the virtual-device test rig's model parameters (relative
#: ranking only — no CPU number is a device metric).  A kind missing here is
#: an error (`device_spec`), never a default.
SPECS: dict[str, DeviceSpec] = {
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
    "cpu": DeviceSpec("cpu", 0.2, 50.0, 10.0, 8e9),
}


def device_spec(device: Optional[jax.Device] = None) -> DeviceSpec:
    """`SPECS` entry of `device` (default: the first JAX device)."""
    device = device or jax.devices()[0]
    try:
        return SPECS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no DeviceSpec for device kind {device.device_kind!r}: add its "
            "published peaks to tracing.SPECS"
        ) from None


# --------------------------------------------------------------------------
# phase scopes + recorder
# --------------------------------------------------------------------------

#: The single source of truth for phase tags (critter symbol names).  Every
#: `scope()` tag must be registered here: the trace tool's device-time
#: buckets (bench/trace.py PHASE_TAGS) and the obs drift classifier both
#: derive from this tuple, so an unregistered tag would silently land in
#: 'other' in every downstream view — scope() refuses it instead.
#: Innermost-first ordering is not required (matching is longest-tag-first
#: downstream); grouping by algorithm keeps the registry reviewable.
PHASE_REGISTRY: tuple[str, ...] = (
    # cholinv (cholesky.py, reference cholinv.hpp:94-136).  CI::buffers is
    # the output-buffer zero-init (pallas zeros_dead_lower) at factor
    # entry — schedule-inserted data movement, tagged so the lint
    # phase-coverage rule and the trace tool attribute it instead of
    # bucketing kernel writes under 'other'.
    "CI::factor_diag", "CI::trsm", "CI::tmu", "CI::inv", "CI::buffers",
    # CI::tail_fused is the fused recursion-tail megakernel
    # (ops/pallas_tpu.fused_tail): an entire plan() subtree — potrf panel,
    # trsm, syrk trailing update, inverse-assembly trmm — lowered as ONE
    # pallas_call with the panel VMEM-resident across phases.  One phase,
    # one price, same rationale as SV::fused_posv.
    "CI::tail_fused",
    # CI::io is what factor() does to its operand and outputs outside the
    # recursion: the identity pad into the padded frame, the persistent
    # layout's permutations, the crop back to n, and (robust) the
    # breakdown scan of the result — so every op of the program carries a
    # phase (docs/OBSERVABILITY.md "Program spans").
    "CI::io",
    # cacqr (qr.py, reference cacqr.hpp:82-116; CQR::scale is historical —
    # kept so old traces/ledgers still bucket).  CQR::recover is the
    # shifted-CholeskyQR escalation path (robust/recovery.py) — present in
    # the program only under a RobustConfig, executed only on breakdown.
    "CQR::gram", "CQR::chol", "CQR::scale", "CQR::merge", "CQR::fused",
    "CQR::formR", "CQR::recover",
    # rectri (inverse.py).  RT::buffers: see CI::buffers.
    "RT::base", "RT::merge", "RT::batch_base", "RT::batch_merge",
    "RT::batch_write", "RT::buffers",
    # trsm (trsm.py)
    "TS::dinv", "TS::leaf", "TS::update",
    # serve (serve/, docs/SERVING.md).  serve::ingest is HOST-side — the
    # per-request fault-injection tap fires on the concrete operand at
    # submit(), never inside a traced program, so a planted fault corrupts
    # exactly one request instead of baking into the AOT executable cache.
    # serve::pad wraps bucket padding; serve::solve wraps the per-problem
    # solve kernels inside the batched executables.
    "serve::ingest", "serve::pad", "serve::solve",
    # batched small-N kernels (ops/batched_small.py).  OP::batched_small
    # wraps the standalone batched-grid potrf/trsm/potrs kernels;
    # SV::fused_posv / SV::fused_lstsq wrap the fused factor+solve paths
    # (factor VMEM-resident between phases — priced as ONE phase because
    # no inter-phase HBM boundary exists to attribute across).
    "OP::batched_small", "SV::fused_posv", "SV::fused_lstsq",
    # continuous-batching scheduler (serve/scheduler.py, docs/SERVING.md).
    # SV::stage wraps host->device staging of padded operands ahead of
    # dispatch (jax.device_put at submit time, plus the in-program operand
    # normalization of the staged-dispatch lint target); SV::dispatch wraps
    # the batched bucket dispatch itself — the boundary the queue_wait /
    # device latency split in serve/stats.py measures across.
    "SV::stage", "SV::dispatch",
    # block-tridiagonal chain (models/blocktri.py, docs/SERVING.md).  The
    # scopes wrap the lax.scan CALLS at the models layer, not the scan
    # bodies: an emit inside a scan body would fire once at trace time
    # while the kernel executes nsteps times, so the whole chain is priced
    # outside the scan and the lint phase-inheritance rule extends the
    # scope over the scanned kernels.  BT::factor covers the Schur-
    # complement factor chain (fused with the forward sweep in
    # posv_blocktri — one phase, one price, the SV::fused_posv rationale);
    # BT::solve covers the block-bidiagonal substitution sweeps.
    "BT::factor", "BT::solve",
    # online factor maintenance (ops/update_small.py, models/blocktri.py
    # extend, docs/SERVING.md "Factor residency").  UP::update /
    # UP::downdate wrap the rank-k hyperbolic-rotation Cholesky
    # update/downdate kernels (one scope per public call, priced whole —
    # chol_update_flops); UP::extend wraps the blocktri chain-extension
    # scan at the models layer (same outside-the-scan emit rationale as
    # BT::factor: the scan body executes nsteps times, the price fires
    # once).
    "UP::update", "UP::downdate", "UP::extend",
    # partitioned (Spike / one-level cyclic-reduction) chain solve
    # (models/blocktri.py impl='partitioned', docs/PERF.md round 13).
    # BT::partition wraps the embarrassingly-parallel per-partition work —
    # the interior factor + widened [B | F | G] spike solves with the
    # partition axis folded into the batched grid, and the final
    # back-substitution — priced whole via blocktri_partition_flops.
    # BT::reduce wraps the P-block interface system: the Schur assembly
    # gemms plus the sequential reduced-chain posv
    # (blocktri_reduce_flops).  Same outside-the-scan emit rationale as
    # BT::factor.
    "BT::partition", "BT::reduce",
    # mixed-precision iterative refinement (robust/refine.py,
    # docs/ROBUSTNESS.md "escalation ladder").  IR::residual wraps the
    # high-precision residual r = B − A·X (and the Aᵀr semi-normal
    # product on the lstsq path); IR::correct wraps the correction solve
    # against the low-precision resident factor plus the X += d update.
    # Both scopes fire once per refine() call even though the
    # lax.while_loop body executes a data-dependent number of times: the
    # model prices ONE sweep and the MEASURED iteration counts land in
    # serve request stats (stats.Collector `refine` block) — the
    # outside-the-scan emit rationale of BT::factor.  QR::tsqr wraps the
    # blocked Householder TSQR tree (ops/tsqr.py): leaf panel QRs, the
    # pairwise R-stack reduction levels, and the top-down Q assembly
    # gemms, priced whole via tsqr_flops.
    "IR::residual", "IR::correct", "QR::tsqr",
    # block-arrowhead completion (models/arrowhead.py, docs/SERVING.md
    # "posv_arrowhead").  The chain half of the factorization rides
    # models/blocktri UNCHANGED and keeps emitting its own BT::* phases
    # (the widened [RHS | Bᵀ] forward/backward sweeps are priced there at
    # k + s columns); the AH::* tags price only the completion work the
    # arrowhead adds on top.  AH::schur wraps the Schur-complement
    # assembly S̃ = S − B·T⁻¹·Bᵀ (one batched border gemm) plus the dense
    # corner Cholesky; AH::border wraps the corner RHS correction, the
    # (s, s) triangular corner solves, and the chain back-substitution
    # x_T = Z − Z_B·x_S.  Emits fire outside every scan (the chain scans
    # live inside blocktri) — the BT::factor rationale.
    "AH::schur", "AH::border",
    # streaming state-space sessions (serve/sessions.py, docs/SERVING.md
    # "Streaming sessions").  SS::extend wraps the session open/append
    # chain-extension program, SS::solve the resident-factor sweep
    # program; both price the whole chain OUTSIDE the interior
    # blocktri scans (the BT::factor rationale), and the interior
    # blocktri calls trace muted() so the work is priced exactly once —
    # under the SS::* tag the session stats attribute by.  The
    # session_contract/close ops are host-side (a pure factor slice plus
    # residency bookkeeping) and execute zero device flops: no phase.
    "SS::extend", "SS::solve",
)
_PHASE_SET: set[str] = set(PHASE_REGISTRY)


def register_phase(tag: str) -> str:
    """Register an out-of-tree phase tag so `scope()` accepts it.  Returns
    the tag for inline use.  Downstream tooling picks it up through
    `PHASE_REGISTRY` on next import — in-process registrations extend the
    live set immediately."""
    global PHASE_REGISTRY
    if tag not in _PHASE_SET:
        PHASE_REGISTRY = PHASE_REGISTRY + (tag,)
        _PHASE_SET.add(tag)
    return tag


_SCOPE_STACK: list[str] = []
_ACTIVE: list["Recorder"] = []
_MUTED: list[bool] = []


def current_scope() -> str | None:
    """Innermost active phase tag, or None outside every scope().  This is
    the key the fault-injection taps (robust/faultinject.py) resolve their
    site against — exposed as a function so callers never reach into the
    stack directly."""
    return _SCOPE_STACK[-1] if _SCOPE_STACK else None


@contextlib.contextmanager
def muted():
    """Suppress emit()/note() attribution for the enclosed trace region.

    The robust recovery branches (robust/recovery.guarded_chol, the sCQR3
    escalation in models/qr.py) re-trace the same phase ops inside a
    lax.cond — at runtime only the taken branch executes, but trace-time
    emits would fire for BOTH, double-counting the cost model and poisoning
    the model-vs-compiled drift gate for the healthy path the model is
    meant to price.  Recovery work is therefore traced muted: the model
    describes the healthy path, the audit sees the full program."""
    _MUTED.append(True)
    try:
        yield
    finally:
        _MUTED.pop()


@dataclasses.dataclass
class PhaseStats:
    """Accumulated model costs for one phase tag (one critter symbol).

    Three compute views, mirroring critter's decomposition
    (reference autotune/util.h:63-127, tune.cpp:79-82):

    * ``flops`` — the homogeneous model count (dense work / devices); what
      the round-1/2 tables reported and what the time estimator uses.
    * ``flops_vol`` — volumetric EXECUTED flops per device (mean over the
      mesh): dead-block skipping counts here.
    * ``flops_max`` — max-per-process executed flops: what the
      critical-path device runs.  With block-distributed triangular
      operands this exceeds flops_vol by up to ~2x (the imbalance the
      reference's element-cyclic layout avoids, structure.hpp:80-85) —
      the column that makes that cost visible (VERDICT r2 #4).
    Emitters that don't distinguish (dense ops, single device) leave both
    equal to ``flops``.

    ``copy_bytes`` is the HBM traffic of pure data-movement the schedule
    inserts around the matmuls — masked triangle materializations
    (masking.take_triangle), window slices, transpose materializations, and
    dynamic_update_slice write-backs (each priced as read + write of the
    moved array).  The pallas view/alias kernels and the in-place explicit
    route drive this term to ~0 (ISSUE 3); the materializing paths emit it
    so autotune ranks the copy-free spelling and the trace tool's `copy`
    bucket has a model-side counterpart.
    """

    calls: int = 0
    flops: float = 0.0  # homogeneous model flops, per device
    comm_bytes: float = 0.0  # collective bytes moved, per device
    collectives: int = 0  # collective count (synchronization/latency terms)
    flops_vol: float = 0.0  # executed, volumetric mean per device
    flops_max: float = 0.0  # executed, max over devices (critical path)
    copy_bytes: float = 0.0  # HBM bytes of schedule-inserted copies, per device

    def merge(self, other: "PhaseStats") -> None:
        self.calls += other.calls
        self.flops += other.flops
        self.comm_bytes += other.comm_bytes
        self.collectives += other.collectives
        self.flops_vol += other.flops_vol
        self.flops_max += other.flops_max
        self.copy_bytes += other.copy_bytes


@contextlib.contextmanager
def scope(tag: str):
    """Enter an algorithm phase: named XLA scope + cost-model attribution.

    Tags follow the reference's symbol names (``CI::trsm``, ``CQR::gram``,
    cholinv.hpp:94-136, cacqr.hpp:82-116) and must be registered in
    `PHASE_REGISTRY` (or via `register_phase`): the device-trace tool and
    the drift classifier bucket by the registry, so an unknown tag would
    silently report under 'other' — refused here at trace time instead.
    """
    if tag not in _PHASE_SET:
        raise ValueError(
            f"unregistered phase tag {tag!r}: add it to "
            "tracing.PHASE_REGISTRY (or register_phase) so the trace tool "
            "and drift classifier can bucket it"
        )
    _SCOPE_STACK.append(tag)
    try:
        with jax.named_scope(tag.replace("::", ".")):
            yield
    finally:
        _SCOPE_STACK.pop()


@contextlib.contextmanager
def host_scope(tag: str):
    """`scope` around eager host-side work, plus a program span of the
    same name (obs/spans.py): eager ops compile once per shape and keep no
    scope of their own, so the span is what sets them under `tag` in a
    profiler trace."""
    with scope(tag), spans.span(tag):
        yield


def kernel_name(kernel: str, phase: str) -> str:
    """The stable name a ``pallas_call`` passes as ``name=``:
    ``<phase>.<kernel>`` in named-scope spelling (``CI.inv.trmm_left``),
    where the phase is the innermost active `scope` — a kernel shared by
    several phases is named under each — else `phase`, the kernel's home
    phase.  The compiled HLO names the custom call after it, and so does
    the device trace."""
    if phase not in _PHASE_SET:
        raise ValueError(f"unregistered home phase {phase!r} for kernel "
                         f"{kernel!r}")
    return f"{active_phase(phase).replace('::', '.')}.{kernel}"


def active_phase(home: str) -> str:
    """The phase a kernel called now is named under: the innermost active
    `scope`, else `home`.  A cached kernel keeps it in its cache key, so a
    kernel shared by two phases is built, and named, once under each."""
    return _SCOPE_STACK[-1] if _SCOPE_STACK else home


def emit(
    flops: float = 0.0,
    comm_bytes: float = 0.0,
    collectives: int = 0,
    flops_vol: float | None = None,
    flops_max: float | None = None,
    copy_bytes: float = 0.0,
) -> None:
    """Attribute model costs to the innermost active phase.

    Called by the SUMMA layer and algorithm base cases at trace time; no-op
    unless a Recorder is active (zero overhead in production paths).
    flops_vol/flops_max (executed volumetric / max-per-process views)
    default to `flops` — the homogeneous assumption.  copy_bytes prices
    schedule-inserted data movement (see PhaseStats)."""
    if not _ACTIVE or _MUTED:
        return
    tag = _SCOPE_STACK[-1] if _SCOPE_STACK else "<top>"
    for rec in _ACTIVE:
        st = rec.stats[tag]
        st.calls += 1
        st.flops += flops
        st.comm_bytes += comm_bytes
        st.collectives += collectives
        st.flops_vol += flops if flops_vol is None else flops_vol
        st.flops_max += flops if flops_max is None else flops_max
        st.copy_bytes += copy_bytes


def note(tag: str) -> None:
    """Count-only event under its own tag (not the scope stack) — used for
    trace-time telemetry like layout-fallback occurrences.  No-op without an
    active Recorder."""
    if _MUTED:
        return
    for rec in _ACTIVE:
        rec.stats[tag].calls += 1


class Recorder:
    """Collects per-phase model costs during one tracing pass.

    Usage::

        with tracing.Recorder() as rec:
            jitted(args)          # first call: traces, recorder captures
        rec.total().flops, rec.stats['CI::trsm'].comm_bytes, ...

    The reference's equivalent is critter's start/stop + get_*_costs
    (tune.cpp:61-82)."""

    def __init__(self) -> None:
        self.stats: dict[str, PhaseStats] = defaultdict(PhaseStats)

    def __enter__(self) -> "Recorder":
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE.remove(self)

    def total(self) -> PhaseStats:
        t = PhaseStats()
        for s in self.stats.values():
            t.merge(s)
        return t

    def estimate_seconds(
        self, spec: Optional[DeviceSpec] = None, dtype=jnp.float32,
        efficiency: float = 0.6, refine_sweeps: float = 1.0,
    ) -> dict[str, tuple[float, float]]:
        """Per-phase (comp_s, comm_s) estimates from the device model.

        efficiency derates peak matmul throughput (achievable fraction).
        The comm term is the full alpha-beta price: bytes/bandwidth (beta)
        plus collectives x alpha — the synchronization count the model
        already tracks; pricing bytes only under-ranked latency-bound
        small-N / high-q configs (each num_chunks slice adds an alpha,
        not bytes).  Schedule-inserted copies (copy_bytes) are local HBM
        traffic, priced at hbm_gbps into the comp term — they spend device
        time, not interconnect time, which is exactly why the copy-free
        explicit route ranks above the materializing one at equal flops.

        refine_sweeps scales the IR::* phases' flops: the model emits ONE
        refinement sweep per refine() call (the while_loop trip count is
        data-dependent — see the IR::* registry note) while the traffic
        actually executes a measured number of them.  Callers price real
        guaranteed-tier traffic by feeding the measured mean from the
        serve stats `refine` block (`refine_sweeps_from_stats`); the
        default 1.0 keeps the historical one-sweep estimate."""
        spec = spec or device_spec()
        peak = spec.peak_tflops(dtype) * 1e12 * efficiency
        out = {}
        for tag, s in self.stats.items():
            comm = s.comm_bytes / (spec.ici_gbps * 1e9) + s.collectives * spec.alpha_s
            flops = s.flops
            if tag.startswith("IR::"):
                flops *= refine_sweeps
            comp = flops / peak + s.copy_bytes / (spec.hbm_gbps * 1e9)
            out[tag] = (comp, comm)
        return out


# --------------------------------------------------------------------------
# analytic collective/compute cost helpers (the alpha-beta model)
# --------------------------------------------------------------------------


def _ring_bytes(block_bytes: float, p: int) -> float:
    """Bytes per device for a ring broadcast/allgather of `block_bytes` over
    an axis of p devices: (p-1)/p * total."""
    return block_bytes * (p - 1) / p if p > 1 else 0.0


def _allreduce_bytes(block_bytes: float, p: int) -> float:
    """Ring allreduce: 2(p-1)/p * bytes (reduce-scatter + allgather)."""
    return 2.0 * block_bytes * (p - 1) / p if p > 1 else 0.0


def gemm_cost(grid, M: int, N: int, K: int, dtype) -> tuple[float, float, int]:
    """(flops, comm_bytes, collectives) per device for a distributed matmul
    C[M,N] = A[M,K] @ B[K,N] under the SUMMA schedule on a dx x dy x c grid.

    Models exactly what the explicit schedule emits
    (parallel/summa.py:_explicit_matmul).  c == 1: a ring all_gather of the
    A block row over axis 'y' and of the B block column over axis 'x' —
    byte-equal to the reference's d per-step ring Bcasts
    (summa.hpp:185-193).  c > 1: per-step masked-psum broadcasts of only
    this layer's d/c panels (2x ring-bcast bytes per panel, c-fold fewer
    panels — the 2.5D comm saving), plus a ring allreduce of the C block
    over depth (summa.hpp:236).  num_chunks splits each of these into that
    many slice collectives (same bytes, more synchronization points — the
    Ibcast/Iallreduce pipeline).  The 'xla' mode compiles to schedules of
    the same family, so the model serves both.
    """
    dx, dy, c = grid.dx, grid.dy, grid.c
    item = jnp.dtype(dtype).itemsize
    p = dx * dy * c
    flops = 2.0 * M * N * K / p
    q = max(1, getattr(grid, "num_chunks", 0))
    d = max(dx, dy)
    c_blk = (M / dx) * (N / dy) * item
    if c == 1:
        a_row = (M / dx) * K * item  # gathered block row per device
        b_col = K * (N / dy) * item  # gathered block column per device
        comm = _ring_bytes(a_row, dy) + _ring_bytes(b_col, dx)
        ncoll = (q if dy > 1 else 0) + (q if dx > 1 else 0)
    else:
        steps = max(1, d // c)  # this layer's K-steps
        a_pan = (M / dx) * (K / d) * item
        b_pan = (K / d) * (N / dy) * item
        comm = steps * (
            _allreduce_bytes(a_pan, dy) + _allreduce_bytes(b_pan, dx)
        )
        ncoll = steps * ((q if dy > 1 else 0) + (q if dx > 1 else 0))
    comm += _allreduce_bytes(c_blk, c)
    # the collect splits into q column slices, but never more than the
    # block has columns (zero-width tails are skipped by the schedule)
    ncoll += min(q, max(1, int(N // max(1, dy)))) if c > 1 else 0
    return flops, comm, ncoll


def transpose_cost(grid, m: int, n: int, dtype) -> tuple[float, int]:
    """(comm_bytes, collectives) per device for a grid transpose: each
    device exchanges its (m/dx, n/dy) block with the mirrored coordinate —
    the reference's pairwise MPI_Sendrecv_replace (util.hpp:232-247), on
    TPU a collective-permute emitted from the layout constraint."""
    dx, dy = grid.dx, grid.dy
    if dx == 1 and dy == 1:
        return 0.0, 0
    item = jnp.dtype(dtype).itemsize
    return (m / dx) * (n / dy) * item, 1


def replicate_cost(grid, m: int, n: int, dtype) -> tuple[float, int]:
    """(comm_bytes, collectives) to replicate an m x n panel to every device
    (all_gather over the whole mesh) — the base-case gather, the analog of
    MPI_Allgather over the slice communicator (cholinv policy.h:176)."""
    p = grid.num_devices
    bytes_total = m * n * jnp.dtype(dtype).itemsize
    return (_ring_bytes(bytes_total, p), 1 if p > 1 else 0)


def allreduce_cost(grid, m: int, n: int, dtype, axes: str = "all") -> tuple[float, int]:
    """(comm_bytes, collectives) for psum of an m x n value.

    axes='all' reduces over the whole mesh (the 1D gram allreduce,
    cacqr.hpp:22); axes='z' over depth only (SUMMA collect, summa.hpp:236)."""
    p = grid.num_devices if axes == "all" else grid.c
    return (_allreduce_bytes(m * n * jnp.dtype(dtype).itemsize, p), 1 if p > 1 else 0)


def potrf_trtri_flops(n: int) -> float:
    """Local panel factor + triangular inverse: n³/3 + n³/3."""
    return 2.0 * n**3 / 3.0


# -- batched small-N kernel pricing (ops/batched_small.py) -----------------
# These count EXECUTED flops, not textbook useful flops: the batched-grid
# kernels run full-matrix masked sweeps (rank-1 outer-product Cholesky,
# one-hot-extraction triangular substitution) because at small n the
# latency is launch/HBM-bound and dense full-width ops are what Mosaic
# lowers well.  The cost model must price what the program does, or the
# obs drift classifier would flag every fused bucket as compiled-extra.


def batched_chol_flops(n: int) -> float:
    """Full-matrix rank-1 sweep Cholesky, per problem: n columns x
    (extract + scale + rank-1 update + accumulate) ≈ 3 dense (n,n)
    products of width one plus the n-wide extraction ≈ 6n³."""
    return 6.0 * n**3


def batched_trsm_flops(n: int, k: int) -> float:
    """One masked substitution sweep, per problem: n columns x (one-hot
    column extract 2n² + row pick/update 4nk) = 2n³ + 4n²k."""
    return 2.0 * n**3 + 4.0 * n**2 * k


def fused_posv_flops(n: int, k: int) -> float:
    """Fused factor + two substitution sweeps, per problem (SV::fused_posv):
    the factor never leaves VMEM, so this is one phase, one price."""
    return batched_chol_flops(n) + 2.0 * batched_trsm_flops(n, k)


def fused_tail_flops(n: int) -> float:
    """Fused recursion-tail megakernel, whole subtree (CI::tail_fused):
    an (n,n) window factored by the masked column-sweep (guarded-rsqrt
    rank-1 updates, ~6n³ executed like batched_chol_flops) plus the
    back-substitution inverse of the n-wide identity (one masked trsm
    sweep at k=n).  Counts EXECUTED kernel flops — the sweep subsumes the
    subtree's potrf/trsm/syrk/trmm phases, so this single price replaces
    every per-phase emit the unfused recursion would have issued."""
    return batched_chol_flops(n) + batched_trsm_flops(n, n)


def blocktri_chol_flops(nblocks: int, b: int) -> float:
    """Block-tridiagonal factor chain, per problem (BT::factor): each of
    `nblocks` chain blocks runs one masked column-sweep Cholesky of the
    (b, b) Schur complement, one forward substitution sweep for
    Wt = L⁻¹·Cᵀ at k=b, the identity-contraction transpose of C (2b³),
    and the Wtᵀ·Wt Schur update (2b³).  Executed flops, like every
    batched-small price — the textbook useful count is nblocks·(b³/3+3b³)
    (the bench driver's numerator)."""
    return nblocks * (batched_chol_flops(b) + batched_trsm_flops(b, b)
                      + 4.0 * b**3)


def blocktri_solve_flops(nblocks: int, b: int, k: int) -> float:
    """ONE block-bidiagonal substitution sweep (forward or backward), per
    problem (BT::solve): per chain block, one (b, b) triangular sweep at
    width k plus the 2b²k off-diagonal coupling product.  A full potrs
    analog is two of these."""
    return nblocks * (batched_trsm_flops(b, k) + 2.0 * b**2 * k)


def blocktri_partition_flops(nblocks: int, b: int, k: int,
                             partitions: int) -> float:
    """Per-partition side of the partitioned (Spike) chain solve, per
    problem (BT::partition): the `nblocks − P` interior blocks factor
    once, run BOTH substitution sweeps at the widened RHS [B | Φ-cols |
    Ψ-cols] of k + 2b columns (the spike solves ride the same sweep as
    the local solutions), and the back-substitution applies the two
    (b, b) spike blocks to each interior solution (4b²k per block).
    Sequential-depth is O(nblocks/P); the WORK stays O(nblocks·b³) plus
    the spike widening — this price is what the bench driver's A/B row
    shows the depth win costs in executed flops."""
    interior = nblocks - partitions
    return (blocktri_chol_flops(interior, b)
            + 2.0 * blocktri_solve_flops(interior, b, k + 2 * b)
            + 4.0 * interior * b**2 * k)


def blocktri_reduce_flops(partitions: int, b: int, k: int) -> float:
    """Reduced interface system of the partitioned chain solve, per
    problem (BT::reduce): per separator, the Schur assembly gemms (three
    (b, b)·(b, b) products into the reduced diagonal/coupling, 6b³, plus
    two (b, b)·(b, k) RHS corrections, 4b²k), then the P-block reduced
    chain runs the ordinary sequential factor + both sweeps."""
    asm = partitions * (6.0 * b**3 + 4.0 * b**2 * k)
    return (asm + blocktri_chol_flops(partitions, b)
            + 2.0 * blocktri_solve_flops(partitions, b, k))


def chol_update_flops(n: int, k: int) -> float:
    """Rank-k Cholesky update/downdate sweep, per problem (UP::update /
    UP::downdate): k rank passes x n hyperbolic rotations, each a one-hot
    row extract (2n²) plus the full-width row write-back outer product
    (2n²) plus the two width-n axpys — ≈ 4kn³ EXECUTED on the masked
    pallas sweep, same executed-flop convention as batched_chol_flops.
    The textbook useful count is ~2kn² (what the bench driver's speedup
    numerator uses); the blocked J-orthogonal XLA path executes
    ~(4p + 4k + 2k²/p)·n² at panel width p."""
    return 4.0 * k * n**3


def fused_lstsq_flops(m: int, n: int, k: int) -> float:
    """Fused batched CholeskyQR2 lstsq, per problem (SV::fused_lstsq):
    gram 2mn² + AᵀB 2mnk, two sweep factors, the R1⁻ᵀ·G·R1⁻¹ correction
    (n-wide fwd sweep + right-solve ≈ 2 trsm sweeps at k=n), three RHS
    sweeps and one back-substitution, plus the triangular R2·R1 product."""
    return (
        2.0 * m * n * (n + k)
        + 2.0 * batched_chol_flops(n)
        + 2.0 * batched_trsm_flops(n, n)
        + 4.0 * batched_trsm_flops(n, k)
        + 2.0 * n**3
    )


def refine_sweep_flops(n: int, k: int) -> float:
    """ONE iterative-refinement sweep over a dense SPD solve, per problem
    (IR::residual + IR::correct): the high-precision residual gemm
    r = B − A·X (2n²k), the two triangular correction sweeps against the
    resident low-precision factor, and the X += d axpy.  The while_loop
    executes this a data-dependent number of times; the model prices one
    sweep (see the IR::* registry note) and the measured counts live in
    serve stats."""
    return 2.0 * n * n * k + 2.0 * batched_trsm_flops(n, k) + 2.0 * n * k


def arrowhead_schur_flops(nblocks: int, b: int, s: int) -> float:
    """Schur-complement completion of the arrowhead corner, per problem
    (AH::schur): the border reduction gemm B·Z_B over the chain
    (2·nblocks·b·s²) plus the dense corner Cholesky of S̃.  The corner
    rides `lax.linalg.cholesky` — a real dense potrf, not a masked sweep —
    so the textbook s³/3 IS the executed count there; the widened chain
    sweeps that produced Z_B are priced inside blocktri under BT::*."""
    return 2.0 * nblocks * b * s * s + s**3 / 3.0


def arrowhead_border_flops(nblocks: int, b: int, s: int, k: int) -> float:
    """Corner solve + chain back-substitution of the arrowhead completion,
    per problem (AH::border): the corner RHS correction y = b_S − B·Z_rhs
    (2·n·s·k over the chain), the two dense (s, s) triangular corner
    solves at width k (2s²k, XLA triangular_solve), and the chain
    back-substitution x_T = Z_rhs − Z_B·x_S (another 2·n·s·k)."""
    n = nblocks * b
    return 4.0 * n * s * k + 2.0 * s * s * k


def refine_sweeps_from_stats(refine_block: Optional[dict]) -> float:
    """Mean executed refinement sweeps per request, read from a serve
    stats `refine` snapshot block (stats.Collector) — the feed for
    `Recorder.estimate_seconds(refine_sweeps=...)`.  Uses the iters p50
    (the typical request's sweep count); absent or malformed blocks fall
    back to the model's one-sweep default, floored at 1.0 because every
    refined request runs at least the residual check sweep."""
    if not refine_block:
        return 1.0
    iters = refine_block.get("iters") or {}
    try:
        return max(float(iters.get("p50", 1.0)), 1.0)
    except (TypeError, ValueError):
        return 1.0


def refine_lstsq_sweep_flops(m: int, n: int, k: int) -> float:
    """ONE semi-normal-equation refinement sweep over lstsq, per problem:
    residual r = B − A·X (2mnk), gram product g = Aᵀr (2mnk), the two
    triangular sweeps of d = R⁻¹R⁻ᵀg, and the update axpy."""
    return 4.0 * m * n * k + 2.0 * batched_trsm_flops(n, k) + 2.0 * n * k


def tsqr_flops(m: int, n: int, leaves: int) -> float:
    """Blocked Householder TSQR, per problem (QR::tsqr): leaf panel QRs
    (Householder sweep + thin-Q assembly ≈ 4·panel·n² each over `leaves`
    panels of m/leaves rows), the pairwise (2n, n) reduction QRs
    (leaves − 1 of them at ≈ 8n³), and the top-down per-level Q-assembly
    gemms (2·panel·n² per leaf per level)."""
    leaves = max(int(leaves), 1)
    levels = max(leaves.bit_length() - 1, 0)
    return (4.0 * m * n**2 + 8.0 * (leaves - 1) * n**3
            + 2.0 * levels * m * n**2)


# --------------------------------------------------------------------------
# cost tables (reference autotune/util.h format family)
# --------------------------------------------------------------------------

def _rows_to_text(rows: list[list]) -> str:
    """Fixed-width table: column width = longest cell + 2 (the reference
    hardcodes setw(15), which its short numeric configs fit; phase-tag
    columns here are longer, so size to content to keep columns aligned)."""
    cells = [[str(c) for c in r] for r in rows]
    width = max((len(c) for r in cells for c in r), default=0) + 2
    return "".join("".join(f"{c:<{width}}" for c in r) + "\n" for r in cells)


def write_times_table(
    path: str,
    rows: list[tuple[str, float, dict[str, tuple[float, float]]]],
) -> None:
    """Measured + estimated per-phase times, one row per config.

    rows: (config_id, measured_wall_s, {tag: (est_comp_s, est_comm_s)}).
    Mirrors the *_cp_times tables (autotune/util.h:4-20): Raw = measured
    wall; per-tag comp/comm estimate columns.
    """
    tags = sorted({t for _, _, est in rows for t in est})
    table = [["Config", "Raw"] + [f"{t}-comp" for t in tags] + [f"{t}-comm" for t in tags]]
    for cid, wall, est in rows:
        table.append(
            [cid, f"{wall:.6f}"]
            + [f"{est.get(t, (0, 0))[0]:.6f}" for t in tags]
            + [f"{est.get(t, (0, 0))[1]:.6f}" for t in tags]
        )
    with open(path, "w") as f:
        f.write(_rows_to_text(table))


def write_costs_table(path: str, rows: list[tuple[str, Recorder]]) -> None:
    """Model cost decomposition per config: flops / comm bytes / collective
    count per phase — the *_cp_costs analog (autotune/util.h:21-29):
    comp ↔ Decomp-comp, comm bytes ↔ Decomp-BSPcomm, collectives ↔ synch —
    plus critter's other two compute views (util.h:63-127, tune.cpp:79-82):
    comp-vol (volumetric executed, mean per device) and comp-max
    (max-per-process, the critical-path device; with block-distributed
    triangular operands up to ~2x comp-vol — see summa.tri_fractions) —
    plus the copy column (copy_bytes: schedule-inserted HBM data movement;
    ~0 on the view/alias routes, docs/OBSERVABILITY.md)."""
    tags = sorted({t for _, rec in rows for t in rec.stats})
    table = [
        ["Config"]
        + [f"{t}-comp" for t in tags]
        + [f"{t}-comp-vol" for t in tags]
        + [f"{t}-comp-max" for t in tags]
        + [f"{t}-comm" for t in tags]
        + [f"{t}-synch" for t in tags]
        + [f"{t}-copy" for t in tags]
    ]
    for cid, rec in rows:
        table.append(
            [cid]
            + [f"{rec.stats[t].flops:.3e}" if t in rec.stats else "0" for t in tags]
            + [f"{rec.stats[t].flops_vol:.3e}" if t in rec.stats else "0" for t in tags]
            + [f"{rec.stats[t].flops_max:.3e}" if t in rec.stats else "0" for t in tags]
            + [f"{rec.stats[t].comm_bytes:.3e}" if t in rec.stats else "0" for t in tags]
            + [str(rec.stats[t].collectives) if t in rec.stats else "0" for t in tags]
            + [f"{rec.stats[t].copy_bytes:.3e}" if t in rec.stats else "0" for t in tags]
        )
    with open(path, "w") as f:
        f.write(_rows_to_text(table))
