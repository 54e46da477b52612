"""In-graph breakdown detection for Cholesky factors.

`lax.linalg.cholesky` has no `info` output: on an indefinite input the CPU
LAPACK kernel reports info > 0 and jax converts that to a silent NaN fill;
on TPU the rank-deficient trailing blocks produce NaN/Inf directly.  Either
way the breakdown is recoverable *from the factor itself* — a clean
Cholesky factor has a finite, strictly positive diagonal.  `factor_info`
reduces that predicate to a LAPACK-style int32 scalar that stays inside the
jit program (no host sync), so callers can branch on it with `lax.cond`.

`combine_block_infos` is the shared min-combine that folds PER-WINDOW
in-kernel info scalars (the fused-tail megakernels of models/cholesky.py,
the per-chain-block infos of models/blocktri.py) into one global
LAPACK-convention status.
"""

from __future__ import annotations

import jax.numpy as jnp


def factor_info(R) -> jnp.ndarray:
    """LAPACK `potrf`-style status for a triangular factor R (n x n).

    Returns int32:
      0      -- healthy: finite everywhere, diagonal strictly positive.
      k in [1, n] -- 1-based index of the first non-finite or non-positive
                diagonal entry (the LAPACK convention: the leading (k-1)
                minor factored fine, order k did not).
      n + 1  -- diagonal is clean but an off-diagonal entry is non-finite
                (seen when a NaN contaminates the triangular solve rather
                than the factorization itself).

    Works on either triangle convention (only the diagonal sign matters)
    and is jit/vmap-safe: a pure O(n^2) reduction, no host callback.
    """
    first_bad = diag_info(jnp.diagonal(R))
    off_bad = ~jnp.all(jnp.isfinite(R))
    n = R.shape[-1]
    return jnp.where(
        first_bad > 0,
        first_bad,
        jnp.where(off_bad, jnp.int32(n + 1), jnp.int32(0)),
    ).astype(jnp.int32)


def diag_info(d) -> jnp.ndarray:
    """The diagonal half of `factor_info`, from the diagonal d alone (an
    O(n) check): 0 when every entry is finite and positive, else the
    1-based index of the first that is not."""
    bad = ~(jnp.isfinite(d) & (d > 0))
    # argmax on bool gives the first True; guard with any() so an all-good
    # diagonal maps to 0 rather than index-0's "1".
    return jnp.where(
        jnp.any(bad), jnp.argmax(bad).astype(jnp.int32) + 1, 0
    ).astype(jnp.int32)


def combine_block_infos(info, tail_infos: list, n: int) -> jnp.ndarray:
    """Fold per-window in-kernel info scalars into a global potrf status.

    `info` is the starting global status (a post-hoc `factor_info` of the
    assembled factor, or zeros when no post-hoc scan exists — scalar or
    batched, any int dtype); `tail_infos` is a list of ``(dest, nw, w)``
    triples: a window at 1-based diagonal offset `dest` of local size
    `nw` reported local info `w` (0 healthy, k in [1, nw] first bad
    pivot, nw+1 off-diagonal contamination — shaped like `info`); `n` is
    the global live dimension.

    This is NOT redundant with `factor_info`: a guarded in-kernel sweep
    turns a bad pivot into finite garbage (no NaN fill the post-hoc
    diagonal scan is guaranteed to see), and when the garbage DOES
    overflow, one-hot outer products turn inf into 0·inf NaNs across the
    whole window — including rows factored BEFORE the breakdown — so the
    post-hoc first-bad-diagonal position inside a broken window is
    backward pollution, not the true pivot.  The kernel's own info is
    authoritative there: post-hoc pivot positions that fall inside a
    broken window are dropped first, then every window's candidate merges
    in.  Local w in [1, nw] maps to global pivot dest+w (1-based, ignored
    when it falls in the identity pad beyond n); w == nw+1 maps to the
    global n+1.  The global status is the FIRST bad pivot — the minimum
    over all flagged positions, which also ranks any pivot (<= n) above
    the off-diagonal sentinel n+1, matching the factor_info precedence."""
    for dest, nw, w in tail_infos:
        broken = w.astype(info.dtype) > 0
        inside = (info > dest) & (info <= dest + nw) & (info <= n)
        info = jnp.where(broken & inside, 0, info)
    for dest, nw, w in tail_infos:
        w = w.astype(info.dtype)
        piv = jnp.where((w > 0) & (w <= nw) & (dest + w <= n), dest + w, 0)
        offd = jnp.where(w == nw + 1, jnp.asarray(n + 1, info.dtype), 0)
        cand = jnp.where(piv > 0, piv, offd)
        info = jnp.where(
            info == 0, cand,
            jnp.where(cand == 0, info, jnp.minimum(info, cand)),
        )
    return info
