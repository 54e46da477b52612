"""Plain reference for cacqr-tall: A = QR, QᵀQ = I, R upper with a
positive diagonal (unique for a full-rank A).

It imports nothing of the program.  It takes the operand the benchmark made
and runs CholeskyQR2 in float32 with HIGHEST matmul precision on the same
row-sharded layout (XLA places the Gram's all-reduce), with each Gram's
Cholesky and inverse in float64 on the host.

The control is the same code with every matmul operand rounded to float8
e4m3 (with a power-of-two scale per tensor): the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

import common


def flops(cfg: dict) -> float:
    """Two sweeps of Gram (2mn² each, counted as mn² + mn² with the
    triangular solve) and Cholesky: 4mn² + 2n³/3."""
    m, n = int(cfg["m"]), int(cfg["n"])
    return 4.0 * m * n * n + 2.0 * n**3 / 3.0


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


@functools.partial(jax.jit, static_argnames=("fmt",))
def _gram(x, *, fmt):
    import jax.numpy as jnp
    from jax import lax

    x = _q(x.astype(jnp.float32), fmt)
    return jnp.matmul(x.T, x, precision=lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("fmt", "out"))
def _apply(x, rinv, *, fmt, out):
    import jax.numpy as jnp
    from jax import lax

    x = _q(x.astype(jnp.float32), fmt)
    return jnp.matmul(x, _q(rinv, fmt),
                      precision=lax.Precision.HIGHEST).astype(out)


def _chol_upper(g) -> tuple:
    g = np.asarray(g, np.float64)
    r = np.linalg.cholesky((g + g.T) / 2).T
    return r, np.linalg.inv(r)


def cqr2(A, fmt=None, out=None):
    """(Q, R): Q row-sharded like A, R a float64 host array."""
    import jax.numpy as jnp

    out = out or jnp.float32
    r1, r1i = _chol_upper(_gram(A, fmt=fmt))
    Q1 = _apply(A, jnp.asarray(r1i, jnp.float32), fmt=fmt, out=jnp.float32)
    r2, r2i = _chol_upper(_gram(Q1, fmt=fmt))
    Q = _apply(Q1, jnp.asarray(r2i, jnp.float32), fmt=fmt, out=out)
    del Q1
    return Q, r2 @ r1


def control(cfg: dict, A) -> list:
    """(Q, R) computed in float8, in the program's place."""
    import jax.numpy as jnp

    return list(cqr2(A, fmt="e4m3", out=jnp.bfloat16))


@functools.partial(jax.jit, static_argnames=("p",))
def _shard_gaps(Q, Qref, *, p):
    """Per-shard squared gap and squared norm: rows are split evenly over
    the p devices, so row block i is shard i."""
    import jax.numpy as jnp

    d = (Q.astype(jnp.float32) - Qref).reshape(p, -1, Q.shape[1])
    r = Qref.reshape(p, -1, Q.shape[1])
    return jnp.sum(d * d, axis=(1, 2)), jnp.sum(r * r, axis=(1, 2))


def compare(cfg: dict, A, outs: list) -> dict:
    """Q_gap: the worst shard's ‖Q − Qref‖/‖Qref‖; R_gap: ‖R − Rref‖/‖Rref‖."""
    Q, R = outs
    r_h = np.asarray(R).astype(np.float64)
    del outs[:], R
    Qref, Rref = cqr2(A)
    num, den = (np.asarray(v, np.float64) for v in _shard_gaps(
        Q, Qref, p=len(Q.sharding.device_set)))
    Q.delete()
    del Qref
    return {"Q_gap": float(np.max(np.sqrt(num / den))),
            "R_gap": common.relgap(r_h, Rref)}
