"""The cholinv schedule planner: predicted seconds per config from the
alpha-beta model, used by the autotune `--top-k` prefilter to prune the
measured sweep to the model's frontier before spending device time (the
reference instead measures every config, tune.cpp:239-253).

It walks cholesky.plan's recursion in closed form — flops at the peak,
collective bytes at the link bandwidth, one alpha per collective launch,
and the schedule's copy bytes at HBM bandwidth — without tracing anything,
so ranking a space of hundreds of configs takes milliseconds.
"""

from __future__ import annotations

import numpy as np


def cholinv_predict(
    n: int,
    grid_shape: tuple[int, int, int],
    bc_dims,
    policies,
    peak_flops: float,
    bw_bytes_per_s: float = 4.5e10,
    alpha_s: float = 1e-6,
    itemsize: int = 2,
    split: int = 1,
    complete_inv: bool = True,
    num_chunks: int = 0,
    balance: str | int = "block",
    hbm_bytes_per_s: float = 8.2e11,
):
    """Predicted seconds per (policy, bc) config from the alpha-beta model;
    returns (seconds[num_pol, num_bc], (best_policy_idx, best_bc_idx)).

    num_chunks models the reference's Ibcast/Iallreduce pipelining
    (summa.hpp:196-248): same bytes, chunk-fold more collective launches —
    only the alpha term moves (round-3 deliberately ignored chunks; a
    chunks-axis sweep would have ranked every q identically).

    balance prices the schedule's COPY term (the data motion the cost
    model used to ignore, mirrored from tracing's copy_bytes emissions at
    hbm_bytes_per_s): 'block'/'tile_cyclic' walk the materializing
    explicit schedule (take_triangle masks, window slices, whole-buffer
    dynamic_update_slice round-trips per phase);
    'tile_cyclic_persistent' prices the persistent layout — three
    lifetime permutes on the comm side and band-sized residual motion on
    the copy side.  On a single device the copy term is ~0 either way
    (the d==1 explicit route rides the aliasing pallas kernels)."""
    bcs = np.asarray(list(bc_dims), dtype=np.int64)
    pols = np.asarray([int(getattr(p, "value", p)) for p in policies], dtype=np.int32)
    out = np.empty((len(pols), len(bcs)), dtype=np.float64)
    dx, dy, c = grid_shape
    bal = (
        balance
        if isinstance(balance, int)
        else (1 if balance == "tile_cyclic_persistent" else 0)
    )
    for ip, pol in enumerate(pols):
        for ib, bc in enumerate(bcs):
            out[ip, ib] = config_seconds(
                n, dx, dy, c, peak_flops, bw_bytes_per_s, alpha_s, itemsize,
                int(bc), int(pol), split, complete_inv, num_chunks,
                bal, hbm_bytes_per_s,
            )
    best = int(np.argmin(out))
    return out, (best // len(bcs), best % len(bcs))


def config_seconds(
    n, dx, dy, c, peak, bw, alpha, item, bc, pol, split, complete_inv,
    num_chunks=0, balance=0, hbm=8.2e11,
):
    """Predicted seconds of one config: a (dx, dy, c) grid, base case bc,
    policy value pol, balance 1 for the persistent layout else 0."""
    def ring(b, p):
        return b * (p - 1) / p if p > 1 else 0.0

    def allred(b, p):
        return 2.0 * b * (p - 1) / p if p > 1 else 0.0

    def gemm(M, N, K, tri=0.5):
        # mirrors tracing.gemm_cost: c==1 amortized ring all_gathers; c>1
        # per-step masked-psum broadcasts of the layer's d/c panels.
        # num_chunks: same bytes, q-fold collective launches (alpha term).
        p = dx * dy * c
        d = max(dx, dy)
        fl = tri * 2.0 * M * N * K / p
        if c <= 1:
            comm = ring(M / dx * K * item, dy) + ring(K * N / dy * item, dx)
            nc = (1.0 if dy > 1 else 0.0) + (1.0 if dx > 1 else 0.0)
        else:
            steps = max(1, d // c)
            comm = steps * (
                allred(M / dx * K / d * item, dy)
                + allred(K / d * N / dy * item, dx)
            )
            nc = steps * ((1.0 if dy > 1 else 0.0) + (1.0 if dx > 1 else 0.0))
        comm += allred(M / dx * N / dy * item, c)
        nc += 1.0 if c > 1 else 0.0
        if num_chunks > 1:
            nc *= num_chunks
        return fl, comm, nc

    p = dx * dy * c
    acc = [0.0, 0.0, 0.0, 0.0]  # flops, comm_bytes, collectives, copy_bytes

    def add(t):
        acc[0] += t[0]; acc[1] += t[1]; acc[2] += t[2]

    padded = min(bc, n)
    while padded < n:
        padded *= 2
    P2 = float(padded) * padded  # whole-buffer dus round-trips move this

    def copy(bytes_):
        # schedule-inserted HBM motion, mirroring tracing's copy_bytes
        # emissions (parallel/summa.py, 2.0 = read + write per moved
        # array).  A single device rides the copy-free aliasing kernels —
        # no term at all; that IS the d==1 explicit uplift.
        if p > 1:
            acc[3] += bytes_ * item

    def walk(w, top):
        if w <= bc:
            # replicate + policy-scoped factorization (utils/config.py):
            # policy 1 adds 2 result psums over depth, 2/3 over the mesh
            acc[0] += 2.0 * w**3 / 3.0
            if p > 1:
                panel = w * w * item
                acc[1] += ring(panel, p)
                acc[2] += 1.0
                if pol == 1 and c > 1:
                    acc[1] += 2.0 * allred(panel, c)
                    acc[2] += 2.0
                elif pol >= 2:
                    acc[1] += 2.0 * allred(panel, p)
                    acc[2] += 2.0
            # window extraction + the R/Rinv write-backs: two whole-buffer
            # dus round-trips when materializing, band-sized under the
            # persistent layout
            copy(4.0 * w * w + (8.0 * w * w if balance else 4.0 * P2))
            return
        n1 = max(bc, w >> split)
        m2 = w - n1
        walk(n1, False)
        # TRSM trmm: triangle mask + a_view + trans_a (3 x n1²), b_view
        # (n1 x m2), result into Rp — whole-buffer dus vs band write-back
        add(gemm(n1, m2, n1))
        copy(6.0 * n1 * n1 + 2.0 * n1 * m2
             + (4.0 * n1 * m2 if balance else 2.0 * P2))
        # Schur syrk: operand .T + a_view (2 x n1 m2), symmetrize (4 m2²)
        # + c_view (2 m2²), update back into buf
        add(gemm(m2, m2, n1))
        copy(4.0 * n1 * m2 + 6.0 * m2 * m2
             + (4.0 * m2 * m2 if balance else 2.0 * P2))
        walk(m2, False)
        if complete_inv or not top:
            # completion trmms: T (no out), then side-R into RIp
            add(gemm(n1, m2, n1))
            copy(4.0 * n1 * n1 + 2.0 * n1 * m2)
            add(gemm(n1, m2, m2))
            copy(4.0 * m2 * m2
                 + (4.0 * n1 * m2 if balance else 2.0 * P2))

    if balance and p > 1:
        # persistent layout: three lifetime permutes (A in, R and Rinv
        # out), priced like grid transposes — per-device block exchange
        acc[1] += 3.0 * P2 / (dx * dy) * item
        acc[2] += 3.0
    walk(padded, True)
    return (
        acc[0] / peak + acc[1] / bw + acc[2] * alpha + acc[3] / p / hbm
    )
