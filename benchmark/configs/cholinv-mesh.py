"""Plain reference for cholinv-mesh: A = RᵀR, Rinv = R⁻¹, R upper, with A,
R and R⁻¹ block-distributed over the cell's chips.

It imports nothing of the program.  The algorithm is cholinv-dense's: a
left-looking blocked Cholesky in float32 at HIGHEST matmul precision, A
made again from the benchmark's generator block by block.  No chip holds
the n x n float32 L (17.2 GB at n = 65536): L is kept as column stripes,
one a chip, and stripe s keeps only rows [s·w, n) of its w columns (at
n = 65536 on four chips 4.3, 3.2, 2.1 and 1.1 GB).  A stripe first takes
the earlier stripes' part of its Schur complement, pulling their rows a
block at a time onto its own chip, then factors its own columns.  The
sampled columns of R⁻¹ come from a blocked back substitution against the
same stripes, and R is brought to the host a shard at a time and compared
a row block at a time on the stripe's chip.

The control is the same code with every stored block and every matmul
operand rounded to float8 e4m3 (with a power-of-two scale per block): the
nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
import sys
import time

import jax
import numpy as np

import common


def flops(cfg: dict) -> float:
    """Cholesky (n³/3) plus the full triangular inverse (n³/3)."""
    n = int(cfg["n"])
    return 2.0 * n**3 / 3.0


def sample_cols(n: int, seed: int, k: int) -> np.ndarray:
    """k column indices drawn from the seed, always with the first and the
    last (which depends on every other)."""
    rng = np.random.default_rng(common.mix(seed, 5))
    mid = rng.choice(np.arange(1, n - 1), size=k - 2, replace=False)
    return np.sort(np.concatenate([[0, n - 1], mid])).astype(np.int32)


def _q(x, fmt):
    """Round to float8 e4m3 (fmt "e4m3") after a power-of-two scale that
    puts the block's largest magnitude near 448; identity for fmt None.
    The rounding is done on the bits: XLA on the TPU drops an f32 -> f8 ->
    f32 round trip as a no-op.  (Copied from configs/cholinv-dense.py.)"""
    import jax.numpy as jnp
    from jax import lax

    if fmt is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), jnp.float32(1e-30))
    scale = jnp.exp2(jnp.floor(jnp.log2(448.0 / amax)))
    y = jnp.clip(x * scale, -448.0, 448.0)
    bits = lax.bitcast_convert_type(y, jnp.uint32)
    lsb = (bits >> 20) & 1  # keep 3 of 23 mantissa bits, ties to even
    bits = (bits + jnp.uint32(0x7FFFF) + lsb) & jnp.uint32(0xFFF00000)
    normal = lax.bitcast_convert_type(bits, jnp.float32)
    sub = jnp.round(y * 512.0) / 512.0  # below 2^-6 the step is 2^-9
    return jnp.where(jnp.abs(y) < 2.0**-6, sub, normal) / scale


def _dot(x, y):
    import jax.numpy as jnp
    from jax import lax

    return jnp.matmul(x.astype(jnp.float32), y.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "s0", "w", "fmt"))
def _panel(salt, *, n, s0, w, fmt):
    """Rows [s0, n) of A's columns [s0, s0+w), in float32 (rounded)."""
    gen = common.generator_module()
    return _q(gen.spd_hash_block(n, salt, s0, s0, n - s0, w), fmt)


@functools.partial(jax.jit, donate_argnums=(0,))
def _take_schur(C, rows, top, r0):
    """C[r0:r0+h] -= rows @ topᵀ: one block of an earlier stripe's rows
    (rows [s0 + r0, +h) of it) against its rows [s0, s0 + w)."""
    from jax import lax

    h = rows.shape[0]
    blk = lax.dynamic_slice(C, (r0, 0), (h, C.shape[1]))
    return lax.dynamic_update_slice(C, blk - _dot(rows, top.T), (r0, 0))


@functools.partial(jax.jit, static_argnames=("b", "store", "fmt"),
                   donate_argnums=(0,))
def _factor_stripe(C, *, b, store, fmt):
    """The stripe's own columns, left-looking by column blocks of b: C is
    rows [s0, n) of the stripe's columns, every earlier stripe's part of
    the Schur complement already taken; returns L's stripe (rows [s0, n))."""
    import jax.numpy as jnp
    from jax import lax

    h, w = C.shape

    def column(j, L):
        jb = j * b

        def update(k, Cj):
            lk = lax.dynamic_slice(L, (0, k * b), (h, b))
            ljk = lax.dynamic_slice(L, (jb, k * b), (b, b))
            return Cj - _dot(lk, ljk.T)

        Cj = lax.fori_loop(0, j, update,
                           lax.dynamic_slice(L, (0, jb), (h, b)).astype(
                               jnp.float32))
        Ljj = jnp.linalg.cholesky(lax.dynamic_slice(Cj, (jb, 0), (b, b)))
        below = lax.linalg.triangular_solve(
            Ljj, Cj, left_side=False, lower=True, transpose_a=True)
        r = lax.broadcasted_iota(jnp.int32, Cj.shape, 0)
        col = jnp.where(r >= jb + b, below, 0.0)
        col = lax.dynamic_update_slice(col, Ljj, (jb, 0))
        return lax.dynamic_update_slice(L, _q(col, fmt).astype(store),
                                        (0, jb))

    return lax.fori_loop(0, w // b, column, C.astype(store))


@functools.partial(jax.jit, static_argnames=("n", "b", "fmt"),
                   donate_argnums=(1,))
def _backsub_stripe(L, X, cols, *, n, b, fmt):
    """R⁻¹[:, cols] = L⁻ᵀ E over the stripe's column blocks, bottom up: X
    holds rows [s0, n) of the solution, the rows below the stripe already
    solved."""
    import jax.numpy as jnp
    from jax import lax

    h, w = L.shape
    s0 = n - h
    rows = lax.broadcasted_iota(jnp.int32, (b, X.shape[1]), 0)

    def row(i, X):
        jb = w - (i + 1) * b
        lc = lax.dynamic_slice(L, (0, jb), (h, b))
        e = (rows + (s0 + jb) == cols[None, :]).astype(jnp.float32)
        rhs = e - _dot(lc.T, X)
        ljj = lax.dynamic_slice(lc, (jb, 0), (b, b)).astype(jnp.float32)
        xj = lax.linalg.triangular_solve(ljj, rhs, left_side=True,
                                         lower=True, transpose_a=True)
        return lax.dynamic_update_slice(X, _q(xj, fmt), (jb, 0))

    return lax.fori_loop(0, w // b, row, X)


@functools.partial(jax.jit, static_argnames=("s0",))
def _row_block_gap(L, rows, jl, *, s0):
    """Squared gap and squared norm of R's row block (all n columns, the
    stripe's local columns [jl, jl+b)) against Lᵀ; R's columns below s0
    lie below the diagonal and count whole in the gap."""
    import jax.numpy as jnp
    from jax import lax

    b = rows.shape[0]
    ref = lax.dynamic_slice(L, (0, jl), (L.shape[0], b)).astype(
        jnp.float32).T
    lo = rows[:, :s0].astype(jnp.float32)
    d = rows[:, s0:].astype(jnp.float32) - ref
    return jnp.sum(d * d) + jnp.sum(lo * lo), jnp.sum(ref * ref)


@jax.jit
def _take_cols(x, cols):
    return x[:, cols]


@functools.partial(jax.jit, static_argnames=("h",))
def _rows(x, r0, *, h):
    from jax import lax

    return lax.dynamic_slice(x, (r0, 0), (h, x.shape[1]))


def _devices(cfg: dict) -> list:
    """One chip a stripe: as many as the configuration's grid has."""
    return jax.devices()[:math.prod(int(d) for d in cfg["grid"].split("x"))]


def _stripes(cfg: dict, salt: int, store, fmt) -> list:
    """L as column stripes, stripe s on the s-th chip, holding rows
    [s·w, n) of its w columns."""
    import jax.numpy as jnp

    n, b = int(cfg["n"]), int(cfg["ref_block"])
    devices = _devices(cfg)
    w = n // len(devices)
    if n % (len(devices) * b):
        raise ValueError(f"ref_block {b} x {len(devices)} stripes must tile "
                         f"n={n}")
    salt = jnp.uint32(salt)
    out = []
    for s, dev in enumerate(devices):
        s0 = s * w
        with jax.default_device(dev):
            C = _panel(salt, n=n, s0=s0, w=w, fmt=fmt)
            for t in range(s):
                Lt, t0 = out[t], t * w
                top = jax.device_put(_rows(Lt, jnp.int32(s0 - t0), h=w), dev)
                for r in range(s0, n, b):
                    blk = jax.device_put(_rows(Lt, jnp.int32(r - t0), h=b),
                                         dev)
                    C = _take_schur(C, blk, top, jnp.int32(r - s0))
                del top
            out.append(_factor_stripe(C, b=b, store=store, fmt=fmt))
    return out


def _inverse_cols(stripes, cols_h, n: int, b: int, fmt) -> np.ndarray:
    """R⁻¹[:, cols] on the host: the back substitution walks the stripes
    from the last, X (rows [s·w, n) of the solution) moving with it."""
    import jax.numpy as jnp

    w = n // len(stripes)
    k = cols_h.size
    X = None
    for s in reversed(range(len(stripes))):
        L = stripes[s]
        dev = next(iter(L.devices()))
        top = np.zeros((w, k), np.float32)
        X = jax.device_put(top if X is None else jnp.concatenate(
            [jax.device_put(top, dev), jax.device_put(X, dev)]), dev)
        X = _backsub_stripe(L, X, jax.device_put(jnp.asarray(cols_h), dev),
                            n=n, b=b, fmt=fmt)
    return np.asarray(X)


def _to_host(R) -> np.ndarray:
    """A sharded n x n array on the host, one shard at a time."""
    out = np.empty(R.shape, dtype=R.dtype)
    for sh in R.addressable_shards:
        out[sh.index] = np.asarray(sh.data)
    R.delete()
    return out


def control(cfg: dict, salt: int, seed: int) -> list:
    """(R, R⁻¹[:, sampled columns]) computed in float8, in the program's
    place, on the host."""
    import jax.numpy as jnp

    n, b = int(cfg["n"]), int(cfg["ref_block"])
    cols = sample_cols(n, seed, int(cfg["ref_cols"]))
    st = _stripes(cfg, salt, jnp.bfloat16, "e4m3")
    X = _inverse_cols(st, cols, n, b, "e4m3")
    w = n // len(st)
    R = np.zeros((n, n), dtype=jnp.bfloat16)
    for s, L in enumerate(st):
        R[s * w:(s + 1) * w, s * w:] = np.asarray(L).T
        L.delete()
    return [R, X]


def compare(cfg: dict, salt: int, outs: list, seed: int) -> dict:
    """R_gap = ‖R − Rref‖/‖Rref‖ over all of R; Rinv_gap the same over the
    sampled columns of R⁻¹.  `outs` = [R, Rinv], on the chips or the host
    (Rinv may already be cut to the sampled columns).  Frees the program's
    arrays before the reference runs."""
    import jax.numpy as jnp

    n, b = int(cfg["n"]), int(cfg["ref_block"])
    cols_h = sample_cols(n, seed, int(cfg["ref_cols"]))
    R, Rinv = outs
    t = [time.perf_counter()]
    if Rinv.shape[1] == n:
        ri = _take_cols(Rinv, jnp.asarray(cols_h))
        Rinv.delete()
        Rinv = ri
    ri_h = np.asarray(Rinv).astype(np.float32)
    r_h = R if isinstance(R, np.ndarray) else _to_host(R)
    t.append(time.perf_counter())
    st = _stripes(cfg, salt, jnp.float32, None)
    X = _inverse_cols(st, cols_h, n, b, None)
    t.append(time.perf_counter())
    w = n // len(st)
    num = den = 0.0
    for s, L in enumerate(st):
        dev = next(iter(L.devices()))
        for jb in range(s * w, (s + 1) * w, b):
            dn, dd = _row_block_gap(L, jax.device_put(r_h[jb:jb + b], dev),
                                    jnp.int32(jb - s * w), s0=s * w)
            num, den = num + float(dn), den + float(dd)
        L.delete()
    t.append(time.perf_counter())
    print("benchmark: reference: to host %.3f s, factor %.3f s, compare "
          "%.3f s" % tuple(b - a for a, b in zip(t, t[1:])), file=sys.stderr)
    return {"R_gap": math.sqrt(num / den),
            "Rinv_gap": common.relgap(ri_h, X)}
