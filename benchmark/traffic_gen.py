"""The one general generator: operands and request pools from a traffic file
and a seed.  The program receives only what this makes.

Dense traffic makes its operands on the device from hashes of the element
index, so any block of an operand can be made again by the reference:

* ``spd_hash``: copied from bench.py.  A symmetric splitmix32-style hash of
  (min(i, j), max(i, j), salt), mapped to U[-1, 1)/sqrt(n), plus 3I.  Its
  spectrum lies in about [1.8, 4.2].
* ``tall_hash``: a hash of (i, j, salt) mapped to U[-1, 1), for tall-skinny
  operands.

Serve traffic is a pool of requests made on the host.  Every seed gets the
same multiset of (op, n, nrhs) triples, drawn by quantiles of the traffic's
size distribution; the seed only orders them and draws their values, so a
seed changes the numbers and not the work.
"""

from __future__ import annotations

import numpy as np


def _hash(r, c, salt):
    import jax.numpy as jnp

    h = r * jnp.uint32(0x9E3779B1) ^ c * jnp.uint32(0x85EBCA77)
    h = h + jnp.asarray(salt).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h.astype(jnp.float32) * jnp.float32(2.0**-32)  # [0, 1)


def spd_hash_block(n: int, salt, r0, c0, h: int, w: int, dtype=None):
    """Rows [r0, r0+h) x columns [c0, c0+w) of spd_hash(n, ., salt), in f32
    (or `dtype`).  r0 and c0 may be traced."""
    import jax.numpy as jnp
    from jax import lax

    r = lax.broadcasted_iota(jnp.uint32, (h, w), 0) + jnp.asarray(
        r0, jnp.uint32)
    c = lax.broadcasted_iota(jnp.uint32, (h, w), 1) + jnp.asarray(
        c0, jnp.uint32)
    u = _hash(jnp.minimum(r, c), jnp.maximum(r, c), salt)
    v = (2.0 * u - 1.0) * jnp.float32(1.0 / float(n) ** 0.5)
    v = v + jnp.where(r == c, jnp.float32(3.0), jnp.float32(0.0))
    return v if dtype is None else v.astype(dtype)


def spd_hash(n: int, dtype, salt):
    """The whole n x n operand, as one fused elementwise program."""
    return spd_hash_block(n, salt, 0, 0, n, n, dtype)


def tall_hash(m: int, n: int, dtype, salt):
    """A tall m x n operand with U[-1, 1) entries."""
    import jax.numpy as jnp
    from jax import lax

    r = lax.broadcasted_iota(jnp.uint32, (m, n), 0)
    c = lax.broadcasted_iota(jnp.uint32, (m, n), 1)
    return (2.0 * _hash(r, c, salt) - 1.0).astype(dtype)


# ---------------------------------------------------------------------------
# serve traffic
# ---------------------------------------------------------------------------


def _sizes(dist: dict, count: int) -> np.ndarray:
    """`count` integer sizes at the mid-quantiles of the distribution."""
    u = (np.arange(count) + 0.5) / count
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if dist["kind"] == "log_uniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif dist["kind"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown size distribution {dist['kind']!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def triples(traffic: dict) -> list:
    """The seed-free multiset of (op, n, nrhs) of a solve pool."""
    count = int(traffic["pool"])
    ns = _sizes(traffic["n"], count)
    fixed = np.random.default_rng(0)  # decorrelates op and nrhs from n
    order = fixed.permutation(count)
    weights = traffic["ops"]
    total = sum(weights.values())
    ops = np.empty(count, dtype=object)
    at = 0
    for i, (op, w) in enumerate(sorted(weights.items())):
        k = count - at if i == len(weights) - 1 else round(count * w / total)
        ops[order[at:at + k]] = op
        at += k
    nrhs = traffic["nrhs"]
    ks = np.asarray(nrhs)[fixed.permutation(count) % len(nrhs)]
    return [(str(ops[i]), int(ns[i]), int(ks[i])) for i in range(count)]


def solve_pool(traffic: dict, seed: int) -> list:
    """[(op, A, B)] in the seed's order, numpy arrays of traffic['dtype'].
    posv: A = G·Gᵀ/n + 3I with G standard normal; lstsq: A is
    (rows_per_col·n) x n standard normal.  B is standard normal."""
    rng = np.random.default_rng(int(seed) % 2**63)
    dt = np.dtype(traffic.get("dtype", "float32"))
    rows = int(traffic.get("lstsq_rows_per_col", 4))
    trip = triples(traffic)
    pool = []
    for idx in rng.permutation(len(trip)):
        op, n, k = trip[idx]
        if op == "posv":
            G = rng.standard_normal((n, n), dtype=np.float32)
            A = (G @ G.T / n + 3.0 * np.eye(n, dtype=np.float32)).astype(dt)
            B = rng.standard_normal((n, k), dtype=np.float32).astype(dt)
        elif op == "lstsq":
            A = rng.standard_normal((rows * n, n), dtype=np.float32).astype(dt)
            B = rng.standard_normal((rows * n, k), dtype=np.float32).astype(dt)
        else:
            raise ValueError(f"unknown solve op {op!r}")
        pool.append((op, A, B))
    return pool
