"""Shape bucketing + micro-batch assembly for the solve engine.

Every distinct operand shape is a fresh trace + compile; served traffic with
free-form shapes would recompile forever.  The classic serving answer
(bucketed paddings — the same trick XLA serving stacks use for sequence
lengths) applies cleanly to the CAPITAL solves because the repo already owns
a *structure-safe* pad: `masking.embed_identity_tail` generalizes
cholesky.pad_embed_identity's diag(X, I) embed, so a padded SPD matrix stays
SPD (factors to diag(R, I)) and a padded tall operand keeps full column rank
(the appended unit columns live in appended rows).  Padded right-hand sides
are zero-filled, so the identity tail solves to exact zeros and cropping
recovers the original solution bit-for-bit in exact arithmetic.

A `Bucket` is the padded per-problem shape plus the batch capacity; the
engine compiles ONE executable per bucket at the fixed batch shape
(capacity, *problem) and short batches are topped up with benign identity
fill problems — fixed shapes are the whole point (a dynamic batch dimension
would reintroduce one compile per batch size).

This module is policy-free about ladders: `bucket_for` reads them from the
engine's ServeConfig (duck-typed: .buckets / .rows_buckets / .nrhs_buckets /
.max_batch) so batching never imports engine.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from capital_tpu.ops import masking
from capital_tpu.utils import tracing

OPS = ("posv", "lstsq", "inv", "posv_blocktri", "posv_arrowhead",
       "chol_update", "chol_downdate", "posv_cached", "blocktri_extend")

#: ops that require a resident factor (engine.submit factor_token=...).
FACTOR_OPS = ("chol_update", "chol_downdate", "posv_cached",
              "blocktri_extend")

#: engine-internal bucket op: a posv_cached whose token was NOT resident
#: rides the full (A, B) operands through a 3-output refactor program
#: (X, R, info) so landing can install R — the seeding route, priced as a
#: residency miss.  Never a client-visible submit op.
MISS_OPS = ("posv_cached_miss",)

#: the streaming-session protocol ops (serve/sessions.py, docs/SERVING.md
#: "Streaming sessions") — all require factor_token = session id.
#: session_open and session_append normalize to the ONE engine-internal
#: `session_extend` bucket op (one compiled program serves both: the
#: engine zeroes C[:, 0] and seeds an identity carry for opens);
#: session_solve buckets under its own name with the 4-stack operand
#: packing A = (4, nblocks, b, b) = [D; C; L; Wt].  session_contract and
#: session_close are HOST-side administrative ops (a pure factor slice /
#: a residency release) that never touch a compiled program — they
#: bucket to None and land through the engine's host path.
SESSION_OPS = ("session_open", "session_append", "session_solve",
               "session_contract", "session_close")

#: engine-internal session bucket ops (the compiled halves of SESSION_OPS).
SESSION_BUCKET_OPS = ("session_extend", "session_solve")


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One executable-cache shape class: the padded per-problem operand
    shapes plus the micro-batch capacity.  Hashable (dict key for the
    executable cache and the per-bucket queues)."""

    op: str
    dtype: str
    a_shape: tuple[int, ...]
    b_shape: tuple[int, ...] | None
    capacity: int
    #: requested accuracy tier (robust/refine.TIERS).  Part of the key:
    #: tiers compile DIFFERENT programs (factor dtype, refinement loop),
    #: so same-shape requests at different tiers must land in different
    #: buckets — mixing them would either refine everyone (latency tax on
    #: fast traffic) or no one (silent accuracy downgrade).  Defaulted so
    #: pre-tier constructions and cache keys stay valid.
    tier: str = "balanced"

    @property
    def key(self) -> tuple:
        return (self.op, self.dtype, self.a_shape, self.b_shape,
                self.capacity, self.tier)


def bucket_label(bucket) -> str:
    """Compact human-stable bucket name for span/telemetry tags, e.g.
    ``posv/f32/a256x256/b256x8/c8`` — the key's information without
    tuple-repr noise (and JSON-safe).  Accepts a Bucket or its `.key`
    tuple (the form Responses/stats carry)."""
    if isinstance(bucket, tuple):
        bucket = Bucket(*bucket)
    a = "x".join(str(d) for d in bucket.a_shape)
    b = ("" if bucket.b_shape is None
         else "/b" + "x".join(str(d) for d in bucket.b_shape))
    tier = "" if bucket.tier == "balanced" else f"/{bucket.tier}"
    dt = str(bucket.dtype).replace("float", "f").replace("bfloat", "bf")
    return f"{bucket.op}/{dt}/a{a}{b}/c{bucket.capacity}{tier}"


def _pick(ladder: tuple[int, ...], v: int) -> int | None:
    """Smallest ladder rung >= v, or None (oversize)."""
    best = None
    for r in ladder:
        if r >= v and (best is None or r < best):
            best = r
    return best


def bucket_for(op: str, a_shape, b_shape, dtype: str, cfg,
               *, tier: str = "balanced") -> Bucket | None:
    """Resolve a request's operand shapes to a bucket, or None when any
    dimension exceeds its ladder (the engine then routes the request
    unbatched through the models/ paths — `oversize` policy).

    `tier` stamps the accuracy tier into the bucket key (geometry is
    tier-independent — tiers change the PROGRAM, not the padded shapes).

    lstsq rows bucket at `m + (nb - n)`: the column pad appends one unit
    column PER padded column and each needs its own appended row
    (masking.embed_identity_tail's rows - m >= cols - n contract).

    posv_blocktri packs the chain as A = (2, nblocks, b, b) — A[0] the
    diagonal blocks, A[1] the sub-diagonal blocks (A[1, 0] dead) — and
    B = (nblocks, b, nrhs), bucketing nblocks and b on their own ladders
    (cfg.nblocks_buckets / cfg.block_buckets); nrhs shares the dense
    ladder.

    posv_arrowhead rides the same chain pack for A and ONE packed tail
    operand B = (nblocks·b + s, s + nrhs) (models/arrowhead.pack: columns
    [:s] are the dense system's last s columns [Bᵀ; S], columns [s:] the
    full RHS).  The chain buckets like posv_blocktri, the border width s
    gets its OWN ladder (cfg.border_buckets — s is a structural rank, not
    an RHS count), nrhs shares the dense ladder; the bucketed tail shape
    is (nbb·bb + sb, sb + kb), from which the program re-derives every
    geometry statically.

    The factor-residency ops bucket on the ENGINE-COMPOSED operands, not
    the wire payload: chol_update/chol_downdate as (resident R (n, n),
    V (n, k)) with k on the nrhs ladder; posv_cached as (resident R,
    RHS) with posv's exact geometry (posv_cached_miss: (A, RHS), same
    shapes, different program); blocktri_extend as (appended chain
    (2, nblocks, b, b), resident carry (b, b))."""
    if op not in OPS and op not in MISS_OPS and op not in SESSION_BUCKET_OPS:
        raise ValueError(f"unknown serve op {op!r}; expected one of {OPS}")
    if tier != "balanced":
        from capital_tpu.robust import refine

        if tier not in refine.TIERS:
            raise ValueError(
                f"accuracy_tier must be one of {refine.TIERS}, got {tier!r}"
            )
        b = bucket_for(op, a_shape, b_shape, dtype, cfg)
        return None if b is None else dataclasses.replace(b, tier=tier)
    if op in ("chol_update", "chol_downdate"):
        nb = _pick(cfg.buckets, a_shape[0])
        kb = _pick(cfg.nrhs_buckets, b_shape[1])
        if nb is None or kb is None:
            return None
        return Bucket(op, dtype, (nb, nb), (nb, kb), cfg.max_batch)
    if op in ("posv_cached", "posv_cached_miss"):
        nb = _pick(cfg.buckets, a_shape[0])
        kb = _pick(cfg.nrhs_buckets, b_shape[1])
        if nb is None or kb is None:
            return None
        return Bucket(op, dtype, (nb, nb), (nb, kb), cfg.max_batch)
    if op in ("blocktri_extend", "session_extend"):
        _, nblocks, b, _ = a_shape
        nbb = _pick(cfg.nblocks_buckets, nblocks)
        bb = _pick(cfg.block_buckets, b)
        if nbb is None or bb is None:
            return None
        return Bucket(op, dtype, (2, nbb, bb, bb), (bb, bb),
                      cfg.max_batch)
    if op == "session_solve":
        # 4-stack session pack: [D; C; L; Wt] — the explicit window AND
        # the resident factor in one bucket-shaped operand (api.py
        # `_batched_session_solve`); geometry buckets like posv_blocktri
        _, nblocks, b, _ = a_shape
        nbb = _pick(cfg.nblocks_buckets, nblocks)
        bb = _pick(cfg.block_buckets, b)
        kb = _pick(cfg.nrhs_buckets, b_shape[2])
        if nbb is None or bb is None or kb is None:
            return None
        return Bucket(op, dtype, (4, nbb, bb, bb), (nbb, bb, kb),
                      cfg.max_batch)
    if op == "posv_blocktri":
        _, nblocks, b, _ = a_shape
        nbb = _pick(cfg.nblocks_buckets, nblocks)
        bb = _pick(cfg.block_buckets, b)
        kb = _pick(cfg.nrhs_buckets, b_shape[2])
        if nbb is None or bb is None or kb is None:
            return None
        return Bucket(op, dtype, (2, nbb, bb, bb), (nbb, bb, kb),
                      cfg.max_batch)
    if op == "posv_arrowhead":
        _, nblocks, b, _ = a_shape
        s = b_shape[0] - nblocks * b
        k = b_shape[1] - s
        nbb = _pick(cfg.nblocks_buckets, nblocks)
        bb = _pick(cfg.block_buckets, b)
        sb = _pick(cfg.border_buckets, s)
        kb = _pick(cfg.nrhs_buckets, k)
        if nbb is None or bb is None or sb is None or kb is None:
            return None
        return Bucket(op, dtype, (2, nbb, bb, bb),
                      (nbb * bb + sb, sb + kb), cfg.max_batch)
    if op in ("posv", "inv"):
        n = a_shape[0]
        nb = _pick(cfg.buckets, n)
        if nb is None:
            return None
        if op == "inv":
            return Bucket(op, dtype, (nb, nb), None, cfg.max_batch)
        kb = _pick(cfg.nrhs_buckets, b_shape[1])
        if kb is None:
            return None
        return Bucket(op, dtype, (nb, nb), (nb, kb), cfg.max_batch)
    m, n = a_shape
    nb = _pick(cfg.buckets, n)
    if nb is None:
        return None
    mb = _pick(cfg.rows_buckets, m + (nb - n))
    kb = _pick(cfg.nrhs_buckets, b_shape[1])
    if mb is None or kb is None:
        return None
    return Bucket(op, dtype, (mb, nb), (mb, kb), cfg.max_batch)


def pad_operands(op: str, A, B, bucket: Bucket):
    """Pad one request's concrete operands to the bucket's per-problem
    shapes: identity-tail embed for the factored operand, zero-fill for the
    RHS.  Host-side eager (submit time), under serve::pad (a phase scope
    and a program span) so profiler traces attribute the pad cost to the
    serving layer."""
    with tracing.host_scope("serve::pad"):
        if op == "posv_blocktri":
            return _pad_blocktri(A, B, bucket)
        if op == "posv_arrowhead":
            return _pad_arrowhead(A, B, bucket)
        if op in ("blocktri_extend", "session_extend"):
            return _pad_blocktri_extend(A, B, bucket)
        if op == "session_solve":
            return _pad_session_solve(A, B, bucket)
        if op in ("chol_update", "chol_downdate"):
            # diag(R, I) stays a valid upper factor (of diag(A, I)) and
            # the zero-filled V rows/columns make every padded rotation a
            # t = 0 no-op — the pad is a fixed point of the update, so
            # cropping recovers the true R' exactly
            pa = masking.embed_identity_tail(A, *bucket.a_shape)
            n, k = B.shape
            pb = jnp.pad(B, ((0, bucket.b_shape[0] - n),
                             (0, bucket.b_shape[1] - k)))
            return pa, pb
        pa = masking.embed_identity_tail(A, *bucket.a_shape)
        pb = None
        if bucket.b_shape is not None:
            m, k = B.shape
            pb = jnp.pad(
                B, ((0, bucket.b_shape[0] - m), (0, bucket.b_shape[1] - k))
            )
        return pa, pb


def _pad_blocktri(A, B, bucket: Bucket):
    """Structure-safe pad for the block-tridiagonal chain: every diagonal
    block gets the per-block identity-tail embed diag(D_i, I) (the Schur
    chain preserves diag(·, I) exactly — all products are 0·x or 1·x),
    sub-diagonal and RHS blocks zero-pad, and appended chain blocks are
    pure identity problems with zero couplings — the padded operand stays
    block-tridiagonal SPD and the real blocks' solution is BITWISE the
    unpadded one (the chain is sequential, so trailing identity blocks
    never feed back; their forward/backward carries are exact zeros)."""
    _, nblocks, b, _ = A.shape
    nbb, bb = bucket.a_shape[1], bucket.a_shape[2]
    kb = bucket.b_shape[2]
    pa = jnp.pad(A, ((0, 0), (0, nbb - nblocks),
                     (0, bb - b), (0, bb - b)))
    eye = jnp.eye(bb, dtype=A.dtype)
    # real blocks complete to diag(D_i, I); appended blocks become I
    tail = jnp.where(jnp.arange(bb) >= b, eye, jnp.zeros_like(eye))
    blk = (jnp.arange(nbb) < nblocks)[:, None, None]
    pa = pa.at[0].add(jnp.where(blk, tail, eye))
    pb = jnp.pad(B, ((0, nbb - nblocks), (0, bb - b),
                     (0, kb - B.shape[2])))
    return pa, pb


def _pad_arrowhead(A, P, bucket: Bucket):
    """Structure-safe pad for the block-arrowhead operands: the chain pack
    pads exactly like `_pad_blocktri` (diag(D_i, I) embeds, zero
    couplings, appended identity blocks); in the packed tail operand the
    border columns zero-pad (appended border columns couple to nothing),
    the corner embeds as diag(S, I) (masking.embed_identity_tail), and
    every RHS entry zero-pads.  The padded dense system is
    diag(A_real_embedded, I): the appended border rows are all-zero, so
    the padded Schur complement is diag(S̃, I) and the appended corner
    rows solve to exact zeros.  For chain-LENGTH padding (nblocks only)
    the real solution is BITWISE the unpadded one, the PR 10 chain-pad
    claim extended through the completion: the appended blocks' border
    couplings are exact zeros, so every Schur/back-substitution
    contraction term they add is 0·x (tests/test_arrowhead.py asserts
    it); block-size / border / nrhs padding is tight but not bitwise (the
    contraction lengths change).

    The chain rows of the tail operand are RE-BLOCKED before padding
    (reshape to (nblocks, b, ·), pad each axis, re-flatten): a flat row
    pad would interleave the appended block-tail rows wrongly when
    bb > b."""
    _, nblocks, b, _ = A.shape
    nbb, bb = bucket.a_shape[1], bucket.a_shape[2]
    n_t = nblocks * b
    s = P.shape[0] - n_t
    k = P.shape[1] - s
    sb = bucket.b_shape[0] - nbb * bb
    kb = bucket.b_shape[1] - sb
    pa = jnp.pad(A, ((0, 0), (0, nbb - nblocks),
                     (0, bb - b), (0, bb - b)))
    eye = jnp.eye(bb, dtype=A.dtype)
    tail = jnp.where(jnp.arange(bb) >= b, eye, jnp.zeros_like(eye))
    blk = (jnp.arange(nbb) < nblocks)[:, None, None]
    pa = pa.at[0].add(jnp.where(blk, tail, eye))
    top = P[:n_t].reshape(nblocks, b, s + k)
    ptop = jnp.concatenate(
        [jnp.pad(top[..., :s],
                 ((0, nbb - nblocks), (0, bb - b), (0, sb - s))),
         jnp.pad(top[..., s:],
                 ((0, nbb - nblocks), (0, bb - b), (0, kb - k)))],
        axis=-1).reshape(nbb * bb, sb + kb)
    pbot = jnp.concatenate(
        [masking.embed_identity_tail(P[n_t:, :s], sb, sb),
         jnp.pad(P[n_t:, s:], ((0, sb - s), (0, kb - k)))], axis=-1)
    return pa, jnp.concatenate([ptop, pbot], axis=0)


def _pad_blocktri_extend(A, carry, bucket: Bucket):
    """Structure-safe pad for the chain-extension operands: the appended
    blocks pad exactly like `_pad_blocktri` (diag(D_i, I) embeds, zero
    couplings, appended identity blocks), and the resident carry L_last
    embeds as diag(L_last, I) — a valid lower factor of diag(S_last, I),
    so the first appended block's coupling solve W₁ = C̃₁·L̃₀⁻ᵀ is exact
    block-diagonal arithmetic (the zero-padded C rows never touch the
    identity tail).  Bitwise-inert like every serve pad."""
    _, nblocks, b, _ = A.shape
    nbb, bb = bucket.a_shape[1], bucket.a_shape[2]
    pa = jnp.pad(A, ((0, 0), (0, nbb - nblocks),
                     (0, bb - b), (0, bb - b)))
    eye = jnp.eye(bb, dtype=A.dtype)
    tail = jnp.where(jnp.arange(bb) >= b, eye, jnp.zeros_like(eye))
    blk = (jnp.arange(nbb) < nblocks)[:, None, None]
    pa = pa.at[0].add(jnp.where(blk, tail, eye))
    pcarry = masking.embed_identity_tail(carry, bb, bb)
    return pa, pcarry


def _pad_session_solve(A, B, bucket: Bucket):
    """Structure-safe pad for the session 4-stack [D; C; L; Wt]: the
    window half pads exactly like `_pad_blocktri` (diag(D_i, I) embeds,
    zero couplings, appended identity blocks), and the factor half pads
    CONSISTENTLY with it — diag(L_i, I) is the Cholesky factor of
    diag(S_i, I) and the zero-padded Wt rows/columns keep both solve
    sweeps' padded carries exact zeros, so the real blocks' solution is
    BITWISE the unpadded one and the guaranteed tier's residual operator
    sees residual ≡ 0 on every padded row (zero RHS against identity
    diagonal blocks)."""
    _, nblocks, b, _ = A.shape
    nbb, bb = bucket.a_shape[1], bucket.a_shape[2]
    kb = bucket.b_shape[2]
    pa = jnp.pad(A, ((0, 0), (0, nbb - nblocks),
                     (0, bb - b), (0, bb - b)))
    eye = jnp.eye(bb, dtype=A.dtype)
    tail = jnp.where(jnp.arange(bb) >= b, eye, jnp.zeros_like(eye))
    blk = (jnp.arange(nbb) < nblocks)[:, None, None]
    emb = jnp.where(blk, tail, eye)
    pa = pa.at[0].add(emb)   # D -> diag(D_i, I), appended blocks I
    pa = pa.at[2].add(emb)   # L -> diag(L_i, I), appended blocks I
    pb = jnp.pad(B, ((0, nbb - nblocks), (0, bb - b),
                     (0, kb - B.shape[2])))
    return pa, pb


def fill_problem(bucket: Bucket):
    """The benign problem that tops a short batch up to capacity: an
    identity operand (SPD for posv/inv, orthonormal columns for lstsq —
    its gram is I, so every op factors it cleanly) against a zero RHS.
    For posv_blocktri the fill is the identity CHAIN: identity diagonal
    blocks, zero couplings — every block factors to L = I exactly; the
    arrowhead fill couples that chain to an identity corner through a
    zero border (the whole fill matrix is I)."""
    dt = jnp.dtype(bucket.dtype)
    if bucket.op == "posv_arrowhead":
        _, nbb, bb, _ = bucket.a_shape
        eyes = jnp.broadcast_to(jnp.eye(bb, dtype=dt), (nbb, bb, bb))
        fa = jnp.stack([eyes, jnp.zeros((nbb, bb, bb), dt)])
        sb = bucket.b_shape[0] - nbb * bb
        fb = jnp.zeros(bucket.b_shape, dt)
        fb = fb.at[nbb * bb:, :sb].set(jnp.eye(sb, dtype=dt))
        return fa, fb
    if bucket.op in ("posv_blocktri", "blocktri_extend", "session_extend",
                     "session_solve"):
        _, nbb, bb, _ = bucket.a_shape
        eyes = jnp.broadcast_to(jnp.eye(bb, dtype=dt), (nbb, bb, bb))
        zeros = jnp.zeros((nbb, bb, bb), dt)
        if bucket.op == "session_solve":
            # identity window with its own factor: L = I, Wt = 0 is
            # exactly factor(I-chain), so both sweeps and the residual
            # operator are no-ops on fill slots
            fa = jnp.stack([eyes, zeros, eyes, zeros])
            return fa, jnp.zeros(bucket.b_shape, dtype=dt)
        fa = jnp.stack([eyes, zeros])
        if bucket.op in ("blocktri_extend", "session_extend"):
            # identity carry: extending the identity chain from L = I
            # factors every fill block to L = I exactly
            return fa, jnp.eye(bb, dtype=dt)
        return fa, jnp.zeros(bucket.b_shape, dtype=dt)
    fa = jnp.eye(*bucket.a_shape, dtype=dt)
    fb = None
    if bucket.b_shape is not None:
        fb = jnp.zeros(bucket.b_shape, dtype=dt)
    return fa, fb


def assemble(padded_a, padded_b, bucket: Bucket):
    """Stack per-request padded operands into the bucket's fixed batch
    shape, topping up with fill problems.  Returns (Ab, Bb | None,
    occupancy) — occupancy is the real-request fraction of capacity, the
    number stats.py reports (chronically low occupancy means the flush
    policy or the ladder is mis-tuned)."""
    nreq = len(padded_a)
    if not 0 < nreq <= bucket.capacity:
        raise ValueError(f"{nreq} requests for capacity {bucket.capacity}")
    fa, fb = fill_problem(bucket)
    Ab = jnp.stack(list(padded_a) + [fa] * (bucket.capacity - nreq))
    Bb = None
    if bucket.b_shape is not None:
        Bb = jnp.stack(list(padded_b) + [fb] * (bucket.capacity - nreq))
    return Ab, Bb, nreq / bucket.capacity


def crop(op: str, X, a_shape, b_shape):
    """Slice one padded per-problem solution back to the request's true
    shape (the unpad half of the masking contract: the identity tail's
    rows of X are exact zeros and are dropped here)."""
    if op in ("posv", "posv_cached", "posv_cached_miss"):
        return X[: a_shape[0], : b_shape[1]]
    if op == "lstsq":
        return X[: a_shape[1], : b_shape[1]]
    if op in ("posv_blocktri", "session_solve"):
        return X[: a_shape[1], : a_shape[2], : b_shape[2]]
    if op == "posv_arrowhead":
        # X is the CHAIN half (nbb, bb, kb) — blocked, so plain slicing
        # unpads; the corner half rides the program's extras slot and the
        # engine's arrowhead sink crops + concatenates it (engine.py)
        nblocks, b = a_shape[1], a_shape[2]
        s = b_shape[0] - nblocks * b
        return X[:nblocks, :b, : b_shape[1] - s]
    if op in ("blocktri_extend", "session_extend"):
        # stacked (2, nbb, bb, bb) [L; Wt] back to the appended blocks
        return X[:, : a_shape[1], : a_shape[2], : a_shape[2]]
    # inv / chol_update / chol_downdate: square (n, n) principal window
    return X[: a_shape[0], : a_shape[0]]
