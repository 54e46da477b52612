"""Plain reference for hplmxp-spd: HPL-MxP's acceptance check of a solution.

It imports nothing of the program.  It makes b again from the benchmark's
generator and the solve's salt, and A again ``ref_block`` rows at a time in
the configuration's dtype on the device (the very numbers the program
factored: A's entries are bf16 numbers and b's are float32 numbers, so the
system in float64 is exactly the one stored).  On the host, in NumPy
float64, with x = x_hi + x_lo summed in float64, it computes HPL-MxP's
scaled residual

    hpl_resid = ‖b − A·x‖∞ / ((‖A‖∞·‖x‖∞ + ‖b‖∞) · n · ε),  ε = 2⁻⁵³,

which HPL-MxP accepts below 16 (the cell's limit).  ε is the unit roundoff
of float64, LAPACK's dlamch('E'), as HPL computes it.

The control is the program's answer before any correction sweep: the bf16
factor's own solve.  It misses the check by about eight orders of
magnitude (a bf16 Cholesky solve is accurate to about 1e-2).
"""

from __future__ import annotations

import functools

import jax
import numpy as np

import common

EPS = 2.0**-53


def flops(cfg: dict) -> float:
    """HPL-MxP's rate counts a fixed number of flops per solve; with a
    Cholesky factor in place of LU that count is n³/3 + 2n²."""
    n = int(cfg["n"])
    return n**3 / 3.0 + 2.0 * n**2


@functools.partial(jax.jit, static_argnames=("n", "h", "dtype"))
def _rows(salt, r0, *, n, h, dtype):
    gen = common.generator_module()
    return gen.spd_hash_block(n, salt, r0, 0, h, n, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _rhs(salt, *, n, dtype):
    gen = common.generator_module()
    return gen.tall_hash(n, 1, dtype, salt)[:, 0]


def control(cfg: dict, unrefined) -> list:
    """(x_hi, x_lo) of the program's answer before its first correction
    sweep, in the program's place: `unrefined` runs the program with no
    sweep and returns that pair."""
    return unrefined()


def compare(cfg: dict, salt_a: int, salt_b: int, x_hi, x_lo) -> dict:
    """hpl_resid of x = x_hi + x_lo for the system the salts made."""
    import jax.numpy as jnp

    n, h = int(cfg["n"]), int(cfg["ref_block"])
    dt = jnp.dtype(cfg["dtype"])
    x = np.asarray(x_hi, np.float64) + np.asarray(x_lo, np.float64)
    b = np.asarray(_rhs(jnp.uint32(salt_b), n=n,
                        dtype=jnp.dtype(cfg["rhs_dtype"])), np.float64)
    rmax = anorm = 0.0
    for r0 in range(0, n, h):
        a = np.asarray(_rows(jnp.uint32(salt_a), jnp.uint32(r0), n=n, h=h,
                             dtype=dt)).astype(np.float64)
        r = b[r0:r0 + h] - a @ x
        rmax = max(rmax, float(np.max(np.abs(r))))
        anorm = max(anorm, float(np.max(np.abs(a, out=a).sum(axis=1))))
    den = (anorm * float(np.max(np.abs(x))) + float(np.max(np.abs(b))))
    return {"hpl_resid": rmax / (den * n * EPS)}
