"""Plain reference for cholinv-dense: A = RᵀR, Rinv = R⁻¹, R upper.

It imports nothing of the program.  It makes A again from the benchmark's
generator, block by block, and factors it by a left-looking blocked
Cholesky in float32 at HIGHEST matmul precision (L = Rᵀ, one n x n float32
buffer, column blocks of ``ref_block``).  The sampled columns of R⁻¹ come from a blocked back substitution
against the same L.

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
    f32 round trip as a no-op, and the control then reads like bfloat16."""
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


#: row stripes of the reference: the column blocks of stripe s need only
#: rows [s0, n) of L, a static shape, so the work is about 0.4n³ flops
STRIPES = 8


@functools.partial(jax.jit, static_argnames=("n", "b", "store", "fmt"))
def _factor(salt, cols, *, n, b, store, fmt):
    import jax.numpy as jnp
    from jax import lax

    gen = common.generator_module()
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    nb = n // b
    stripes = max(k for k in range(1, STRIPES + 1) if nb % k == 0)
    width = n // stripes

    def dot(x, y):
        return jnp.matmul(x, y, precision=hi, preferred_element_type=f32)

    L = jnp.zeros((n, n), store)
    for s in range(stripes):
        s0, w = s * width, (s + 1) * width

        def column(j, L, s0=s0):
            jb = j * b

            def update(k, C):
                lk = lax.dynamic_slice(L, (s0, k * b), (n - s0, b))
                ljk = lax.dynamic_slice(L, (jb, k * b), (b, b))
                return C - dot(lk, ljk.T)

            C = _q(gen.spd_hash_block(n, salt, s0, jb, n - s0, b), fmt)
            C = lax.fori_loop(0, j, update, C)
            Ljj = jnp.linalg.cholesky(lax.dynamic_slice(C, (jb - s0, 0),
                                                        (b, b)))
            below = lax.linalg.triangular_solve(
                Ljj, C, left_side=False, lower=True, transpose_a=True)
            r = lax.broadcasted_iota(jnp.int32, C.shape, 0) + s0
            col = jnp.where(r >= jb + b, below, 0.0)
            col = lax.dynamic_update_slice(col, Ljj, (jb - s0, 0))
            return lax.dynamic_update_slice(
                L, _q(col, fmt).astype(store), (s0, jb))

        L = lax.fori_loop(s0 // b, w // b, column, L)

    # R⁻¹[:, cols] = L⁻ᵀ E: back substitution by block rows, bottom up
    E = (jnp.arange(n)[:, None] == cols[None, :]).astype(f32)

    def row(i, X):
        jb = (nb - 1 - i) * b
        lc = lax.dynamic_slice(L, (0, jb), (n, b))
        rhs = lax.dynamic_slice(E, (jb, 0), (b, E.shape[1])) - dot(lc.T, X)
        ljj = lax.dynamic_slice(lc, (jb, 0), (b, b)).astype(f32)
        xj = lax.linalg.triangular_solve(ljj, rhs, left_side=True,
                                         lower=True, transpose_a=True)
        return lax.dynamic_update_slice(X, _q(xj, fmt), (jb, 0))

    X = lax.fori_loop(0, nb, row, jnp.zeros(E.shape, f32))
    return L, X


@functools.partial(jax.jit, static_argnames=("b",))
def _row_block_gap(L, rows, jb, *, b):
    """Squared gap and squared norm of R's row block [jb, jb+b) against Lᵀ."""
    import jax.numpy as jnp
    from jax import lax

    ref = lax.dynamic_slice(L, (0, jb), (L.shape[0], b)).astype(
        jnp.float32).T
    d = rows.astype(jnp.float32) - ref
    return jnp.sum(d * d), jnp.sum(ref * ref)


@jax.jit
def _take_cols(x, cols):
    return x[:, cols]


def control(cfg: dict, salt: int, seed: int) -> list:
    """(R, R⁻¹[:, sampled columns]) computed in float8, in the program's
    place."""
    import jax.numpy as jnp

    n, b = int(cfg["n"]), int(cfg["ref_block"])
    cols = jnp.asarray(sample_cols(n, seed, int(cfg["ref_cols"])))
    L, X = _factor(jnp.uint32(salt), cols, n=n, b=b, store=jnp.bfloat16,
                   fmt="e4m3")
    R = L.T
    L.delete()
    return [R, X]


def compare(cfg: dict, salt: int, outs: list, seed: int) -> dict:
    """R_gap = ‖R − Rref‖/‖Rref‖ over all of R; Rinv_gap the same over the
    sampled columns of R⁻¹.  `outs` = [R, Rinv] (Rinv may already be cut to
    the sampled columns).  Frees them before the reference runs."""
    import jax.numpy as jnp

    n, b = int(cfg["n"]), int(cfg["ref_block"])
    cols_h = sample_cols(n, seed, int(cfg["ref_cols"]))
    cols = jnp.asarray(cols_h)
    R, Rinv = outs
    ri = Rinv if Rinv.shape[1] != n else _take_cols(Rinv, cols)
    t = [time.perf_counter()]
    ri_h = np.asarray(ri.astype(jnp.float32))
    Rinv.delete()
    r_h = np.asarray(R)
    R.delete()
    t.append(time.perf_counter())
    L, X = _factor(jnp.uint32(salt), cols, n=n, b=b, store=jnp.float32,
                   fmt=None)
    L.block_until_ready()
    t.append(time.perf_counter())
    dev = next(iter(L.devices()))
    num = den = 0.0
    for jb in range(0, n, b):
        dn, dd = _row_block_gap(L, jax.device_put(r_h[jb:jb + b], dev),
                                jnp.int32(jb), b=b)
        num, den = num + float(dn), den + float(dd)
    x_h = np.asarray(X)
    del L, X
    t.append(time.perf_counter())
    print("benchmark: reference: to host %.3f s, factor %.3f s, compare "
          "%.3f s" % tuple(b - a for a, b in zip(t, t[1:])), file=sys.stderr)
    return {"R_gap": math.sqrt(num / den),
            "Rinv_gap": common.relgap(ri_h, x_h)}
