"""Triangular-predicated Pallas kernel tests (interpret mode on the CPU rig).

Checks every structure-flag combination of ops/pallas_tpu.tri_matmul against
dense masked references, odd (non-tile-aligned) shapes, and the summa-layer
pallas mode end to end through cholinv (the consumer whose Schur windows
carry upper-triangle-only data)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.ops.pallas_tpu import default_blocks, tri_matmul
from capital_tpu.parallel import summa
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import rand48, residual


@pytest.fixture(scope="module")
def grid1():
    return Grid.square(c=1, devices=jax.devices("cpu")[:1])


@pytest.fixture(scope="module")
def mats():
    rng = np.random.default_rng(0)
    n, m = 300, 200  # deliberately not multiples of 128
    A = jnp.asarray(rng.standard_normal((n, n)))
    B = jnp.asarray(rng.standard_normal((n, m)))
    C = jnp.asarray(rng.standard_normal((m, n)))
    return A, B, C


def _close(got, want, tol=1e-10):
    assert float(jnp.max(jnp.abs(got - want))) < tol


def test_plain_matmul(mats):
    A, B, _ = mats
    _close(tri_matmul(A, B), A @ B)


def test_f32_three_pass_high():
    """precision='high' on f32 operands runs the in-kernel bf16x3
    split-accumulate (VERDICT r3 #3): ~f32-grade accuracy, far better than
    single-pass bf16, no in-kernel error."""
    rng = np.random.default_rng(7)
    n = 256
    A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    want = np.asarray(A, np.float64) @ np.asarray(B, np.float64)
    scale = np.abs(want).max()

    def err(precision):
        got = tri_matmul(A, B, a_uplo="U", precision=precision)
        ref = np.triu(np.asarray(A, np.float64)) @ np.asarray(B, np.float64)
        return float(np.abs(np.asarray(got, np.float64) - ref).max()) / scale

    e_high = err("high")
    e_highest = err("highest")
    e_bf16 = float(
        np.abs(
            np.asarray(
                jnp.matmul(
                    jnp.triu(A).astype(jnp.bfloat16), B.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                ),
                np.float64,
            )
            - np.triu(np.asarray(A, np.float64)) @ np.asarray(B, np.float64)
        ).max()
    ) / scale
    # 3-pass lands within an order of magnitude of full f32 and far below
    # single-pass bf16 (classic split-accumulate error profile)
    assert e_high < 50 * max(e_highest, 1e-9)
    assert e_high < e_bf16 / 20


@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("trans", [False, True])
def test_a_triangular(mats, uplo, trans):
    A, B, _ = mats
    T = jnp.triu(A) if uplo == "U" else jnp.tril(A)
    Top = T.T if trans else T
    _close(tri_matmul(A, B, a_uplo=uplo, a_trans=trans), Top @ B)


@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("trans", [False, True])
def test_b_triangular(mats, uplo, trans):
    A, _, C = mats
    T = jnp.triu(A) if uplo == "U" else jnp.tril(A)
    Top = T.T if trans else T
    _close(tri_matmul(C, A, b_uplo=uplo, b_trans=trans), C @ Top)


@pytest.mark.parametrize("uplo", ["U", "L"])
def test_syrk_out_triangle(mats, uplo):
    _, B, _ = mats
    full = B.T @ B
    want = jnp.triu(full) if uplo == "U" else jnp.tril(full)
    _close(tri_matmul(B, B, a_trans=True, out_uplo=uplo), want, tol=1e-9)


def test_alpha_and_explicit_blocks(mats):
    A, B, _ = mats
    _close(
        tri_matmul(A, B, a_uplo="U", alpha=-2.0, blocks=(128, 128, 128)),
        -2.0 * jnp.triu(A) @ B,
    )


def test_dead_triangle_ignored(mats):
    """Entries in the dead triangle must be treated as zero regardless of
    buffer contents (BLAS trmm contract)."""
    A, B, _ = mats
    garbage = A + jnp.tril(jnp.full_like(A, 1e6), k=-1)
    _close(tri_matmul(garbage, B, a_uplo="U"), jnp.triu(A) @ B)


def test_flag_validation(mats):
    A, B, _ = mats
    with pytest.raises(ValueError, match="at most one"):
        tri_matmul(A, A, a_uplo="U", b_uplo="L")
    with pytest.raises(ValueError, match="out_uplo"):
        tri_matmul(A, A, a_uplo="U", out_uplo="U")
    with pytest.raises(ValueError, match="mismatch"):
        tri_matmul(A, B.T)


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", (1024, 100 * 2**20)),
    ("TPU v4", (512, None)),
    ("TPU v99", None),  # unknown TPU kind: an error, never a default
])
def test_device_budget_reads_scoped_kind(kind, want):
    """The tile budget follows the scoped device's kind (a described chip
    works with no TPU attached) and refuses a kind it has no row for."""
    import types

    from capital_tpu.ops import pallas_tpu

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    with pallas_tpu.device_scope(dev):
        assert pallas_tpu._interpret_default() is False
        if want is None:
            with pytest.raises(ValueError, match="no tile budget"):
                pallas_tpu._device_budget()
        else:
            assert pallas_tpu._device_budget() == want


def test_default_blocks_budget():
    from capital_tpu.ops.pallas_tpu import _device_budget

    cap, _ = _device_budget()  # 512 on the CPU rig, 1024 on v5e+
    bm, bn, bk = default_blocks(8192, 8192, 8192, itemsize=2)
    assert (bm, bn) == (cap, cap) and bk >= cap
    # f32 halves the dtype K-budget on every chip class (VMEM headroom)
    assert default_blocks(8192, 8192, 8192, itemsize=4)[2] <= 1024
    # skinny output tiles afford a deep K panel regardless of chip cap
    assert default_blocks(8192, 100000, 256, itemsize=2)[2] == 2048
    # small operands shrink to their padded size
    assert default_blocks(100, 100, 100) == (128, 128, 128)
    assert default_blocks(300, 8192, 8192)[0] == 384


def test_summa_trmm_pallas_mode(grid1, mats):
    A, B, _ = mats
    out = summa.trmm(
        grid1, A, B, summa.TrmmArgs(side="L", uplo="U", trans_a=True),
        mode="pallas",
    )
    _close(out, jnp.triu(A).T @ B)


def test_summa_syrk_pallas_mode_fused_beta(grid1, mats):
    """beta*C accumulates inside the kernel: the live (uplo) triangle carries
    alpha*AᵀA + beta*C; the dead half is UNDEFINED by contract (callers read
    only the live triangle — cholinv's Schur consumer does)."""
    A, B, _ = mats
    C0 = jnp.asarray(np.random.default_rng(1).standard_normal((B.shape[1],) * 2))
    out = summa.syrk(
        grid1, B, C0, summa.SyrkArgs(trans=True, alpha=-1.0, beta=1.0),
        mode="pallas",
    )
    want_upper = jnp.triu(-(B.T @ B) + C0)
    _close(jnp.triu(out), want_upper, tol=1e-9)


def test_tri_matmul_fused_beta_views():
    """Aligned in-kernel beta*C with every operand a window of a larger
    buffer — the exact shape of cholinv's Schur update at 128-multiples."""
    rng = np.random.default_rng(2)
    buf = jnp.asarray(rng.standard_normal((512, 512)))
    Rp = jnp.asarray(rng.standard_normal((512, 512)))
    got = tri_matmul(
        Rp, Rp, a_trans=True, b_trans=False, out_uplo="U", alpha=-1.0,
        a_view=(128, 256, 128, 256), b_view=(128, 256, 128, 256),
        c=buf, c_view=(256, 256, 256, 256), beta=1.0,
        blocks=(128, 128, 128),  # multi-tile: 2x2 output, 3 live tiles
    )
    R12 = Rp[128:256, 256:512]
    want = jnp.triu(-(R12.T @ R12) + buf[256:512, 256:512])
    _close(jnp.triu(got), want)
    # misaligned windows fall back to materializing but keep the same live
    # triangle
    got2 = tri_matmul(
        Rp, Rp, a_trans=True, b_trans=False, out_uplo="U", alpha=-1.0,
        a_view=(100, 200, 100, 200), b_view=(100, 200, 100, 200),
        c=buf, c_view=(200, 200, 200, 200), beta=1.0,
    )
    R12m = Rp[100:200, 200:400]
    wantm = jnp.triu(-(R12m.T @ R12m) + buf[200:400, 200:400])
    _close(jnp.triu(got2), wantm)


def test_tri_matmul_inplace_rmw_syrk():
    """In-place tri-output RMW: out IS the C buffer — live tiles are read,
    updated, and written back at the same offsets; every untouched region of
    the buffer (outside the window, and the window's dead half on the
    aligned path) is preserved.  This is the no-Schur-chain memory mode of
    cholinv (schur_in_place)."""
    rng = np.random.default_rng(7)
    buf = jnp.asarray(rng.standard_normal((512, 512)))
    Rp = jnp.asarray(rng.standard_normal((512, 512)))
    got = tri_matmul(
        Rp, Rp, a_trans=True, b_trans=False, out_uplo="U", alpha=-1.0,
        a_view=(128, 256, 128, 256), b_view=(128, 256, 128, 256),
        c=buf, c_view=(256, 256, 256, 256), beta=1.0,
        out=buf, out_off=(256, 256),
        blocks=(128, 128, 128),  # multi-tile: 2x2 output window, 3 live tiles
    )
    assert got.shape == buf.shape
    R12 = Rp[128:256, 256:512]
    want = jnp.triu(-(R12.T @ R12) + buf[256:512, 256:512])
    _close(jnp.triu(got[256:512, 256:512]), want)
    # untouched regions of the buffer survive the aliased write
    _close(got[:256, :], buf[:256, :])
    _close(got[256:, :256], buf[256:, :256])
    # aligned kernel path: the window's dead (strictly-lower) tiles are
    # never visited, so they keep the ORIGINAL buffer contents — here the
    # (1, 0) tile of the 2x2 window
    _close(got[384:512, 256:384], buf[384:512, 256:384])

    # shifted-window / non-C out combinations are rejected, not mis-written
    with pytest.raises(ValueError, match="out to BE the C operand"):
        tri_matmul(
            Rp, Rp, a_trans=True, out_uplo="U",
            a_view=(128, 256, 128, 256), b_view=(128, 256, 128, 256),
            c=buf, c_view=(256, 256, 256, 256), beta=1.0,
            out=buf, out_off=(0, 0),
        )

    # misaligned windows: the materializing fallback writes the full window
    # (dead half = beta*C, the documented fallback behavior) but preserves
    # everything outside it
    got2 = tri_matmul(
        Rp, Rp, a_trans=True, b_trans=False, out_uplo="U", alpha=-1.0,
        a_view=(100, 200, 100, 200), b_view=(100, 200, 100, 200),
        c=buf, c_view=(200, 200, 200, 200), beta=1.0,
        out=buf, out_off=(200, 200),
    )
    R12m = Rp[100:200, 200:400]
    wantm = jnp.triu(-(R12m.T @ R12m) + buf[200:400, 200:400])
    _close(jnp.triu(got2[200:400, 200:400]), wantm)
    _close(got2[:200, :], buf[:200, :])


def test_summa_syrk_in_place_modes(grid1):
    """summa.syrk(in_place=True) agrees with the out-of-place result across
    pallas and xla modes (window write-back semantics only differ in where
    the result lands)."""
    rng = np.random.default_rng(8)
    buf = jnp.asarray(rng.standard_normal((256, 256)))
    A = jnp.asarray(rng.standard_normal((256, 256)))
    args = summa.SyrkArgs(trans=True, alpha=-1.0, beta=1.0)
    for mode in ("pallas", "xla"):
        got = summa.syrk(
            grid1, A, buf, args, mode=mode,
            a_view=(0, 128, 128, 128), c_view=(128, 128, 128, 128),
            in_place=True,
        )
        R12 = A[0:128, 128:256]
        want = jnp.triu(-(R12.T @ R12) + buf[128:256, 128:256])
        _close(jnp.triu(got[128:, 128:]), want, tol=1e-9)
        _close(got[:128, :], buf[:128, :])


def test_tri_matmul_fused_beta_promotes_c_dtype():
    """Mixed dtypes: a wider C promotes the result exactly like the unfused
    `AB + beta*C` (mode='xla') would — on the aligned kernel path and the
    misaligned fallback alike."""
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((256, 256)), jnp.bfloat16)
    C = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    # the aligned kernel path adds C onto the f32 accumulator (slightly
    # BETTER than unfused); the misaligned fallback first rounds the product
    # to the operands' bf16 — exactly what unfused mode='xla' produces.
    # Each path gets its own bit-matched reference so tolerances stay tight.
    def product(a):
        return jnp.matmul(a.T, a, preferred_element_type=jnp.float32)

    got = tri_matmul(A, A, a_trans=True, out_uplo="U", c=C, beta=1.0)
    assert got.dtype == jnp.float32
    _close(jnp.triu(got), jnp.triu(product(A) + C), tol=1e-3)
    got2 = tri_matmul(
        A[:200, :200], A[:200, :200], a_trans=True, out_uplo="U",
        c=C[:200, :200], beta=1.0,
    )
    assert got2.dtype == jnp.float32
    want2 = product(A[:200, :200]).astype(jnp.bfloat16).astype(jnp.float32)
    # the kernel's blocked f32 accumulation and jnp.matmul's order can land
    # on opposite sides of a bf16 rounding boundary, so individual entries
    # may differ by one ulp (~1.0 at these ~200 magnitudes); boundary hits
    # are rare, so the MEAN stays tiny unless the beta*C term or a window
    # is actually wrong (dropping C would shift the mean by ~0.8)
    diff = jnp.abs(jnp.triu(got2) - jnp.triu(want2 + C[:200, :200]))
    assert float(jnp.max(diff)) < 1.5
    assert float(jnp.mean(diff)) < 0.01


def test_cholinv_pallas_mode_end_to_end(grid1):
    n = 192
    A = jnp.asarray(rand48.symmetric(n))
    cfg = cholesky.CholinvConfig(base_case_dim=64, mode="pallas")
    R, Rinv = jax.jit(lambda a: cholesky.factor(grid1, a, cfg))(A)
    assert float(residual.cholesky_residual(A, R)) < 1e-13
    assert float(residual.cholesky_inverse_residual(R, Rinv)) < 1e-13


def test_cholinv_schur_in_place_matches_default(grid1):
    """schur_in_place=True (the no-Schur-chain memory mode that fits n=49152
    on one v5e) must produce the same factor/inverse as the default — on the
    aligned pallas path (views + aliased RMW end to end) and on xla mode,
    and on a misaligned size that exercises the fallbacks."""
    for n, bc, mode in ((512, 128, "pallas"), (512, 128, "xla"), (192, 64, "pallas")):
        A = jnp.asarray(rand48.symmetric(n))
        base = cholesky.CholinvConfig(base_case_dim=bc, mode=mode)
        inpl = cholesky.CholinvConfig(
            base_case_dim=bc, mode=mode, schur_in_place=True
        )
        R0, RI0 = jax.jit(lambda a: cholesky.factor(grid1, a, base))(A)
        R1, RI1 = jax.jit(lambda a: cholesky.factor(grid1, a, inpl))(A)
        np.testing.assert_array_equal(np.asarray(R0), np.asarray(R1))
        np.testing.assert_array_equal(np.asarray(RI0), np.asarray(RI1))
        assert float(residual.cholesky_residual(A, R1)) < 1e-13


def test_cholinv_out_buffers_reuse(grid1):
    """factor(out_buffers=...): factoring into a PREVIOUS factor's outputs
    (the benchmark-loop carry that kills the hoisted-zeros copies) must give
    exactly the fresh-buffer result — every upper tile rewritten, dead lower
    zeros preserved."""
    # n = bc·2^k shapes only: with padding (p != n) factor returns CROPPED
    # arrays that cannot serve as the next call's p x p buffers
    for n, bc, mode in ((512, 128, "pallas"), (256, 64, "xla")):
        cfg = cholesky.CholinvConfig(base_case_dim=bc, mode=mode)
        A1 = jnp.asarray(rand48.symmetric(n))
        A2 = jnp.asarray(rand48.symmetric(n)) + 0.5 * jnp.eye(n)

        def chain(a1, a2):
            bufs = cholesky.factor_buffers(grid1, n, a1.dtype, cfg)
            R1, RI1 = cholesky.factor(grid1, a1, cfg, out_buffers=bufs)
            # second factor reuses the first's outputs as its buffers
            return cholesky.factor(grid1, a2, cfg, out_buffers=(R1, RI1))

        R2, RI2 = jax.jit(chain)(A1, A2)
        Rf, RIf = jax.jit(lambda a: cholesky.factor(grid1, a, cfg))(A2)
        np.testing.assert_array_equal(np.asarray(R2), np.asarray(Rf))
        np.testing.assert_array_equal(np.asarray(RI2), np.asarray(RIf))
    # contract violations are rejected
    cfg = cholesky.CholinvConfig(base_case_dim=64, complete_inv=False)
    with pytest.raises(ValueError, match="complete_inv"):
        cholesky.factor(
            grid1, jnp.asarray(rand48.symmetric(128)), cfg,
            out_buffers=(jnp.zeros((128, 128)), jnp.zeros((128, 128))),
        )


def test_cholinv_pallas_mode_aligned_views(grid1):
    """bc=128 at n=512: every window size/offset is a multiple of 128, so
    this drives the ALIGNED in-place path end to end — offset index maps for
    the trmm/syrk operand views and aliased `out`/`out_off` writes for the
    leaf transposes, TRSM, and inverse completion (the n=192/bc=64 test
    above always takes the _fit_block==0 materializing fallback, which
    would mask a regression in the aligned kernels)."""
    n = 512
    A = jnp.asarray(rand48.symmetric(n))
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas")
    R, Rinv = jax.jit(lambda a: cholesky.factor(grid1, a, cfg))(A)
    assert float(residual.cholesky_residual(A, R)) < 1e-13
    assert float(residual.cholesky_inverse_residual(R, Rinv)) < 1e-13
    # dead halves must be true zeros (mask inside the aliased writes)
    assert float(jnp.abs(jnp.tril(R, -1)).max()) == 0.0
    assert float(jnp.abs(jnp.tril(Rinv, -1)).max()) == 0.0


class TestWriteDiagBlocks:
    """In-place aliased diagonal-block scatter (round 5 — the rectri
    batched-prefix write-back)."""

    def test_aligned_kernel_path(self):
        from capital_tpu.ops import pallas_tpu

        rng = np.random.default_rng(0)
        out = jnp.asarray(rng.standard_normal((512, 512)).astype(np.float32))
        W = jnp.asarray(rng.standard_normal((4, 128, 128)).astype(np.float32))
        # `out` is consumed (aliased donation): snapshot the expectation
        # BEFORE the call
        want = np.asarray(out).copy()
        for i in range(4):
            want[i * 128:(i + 1) * 128, i * 128:(i + 1) * 128] = np.asarray(W[i])
        got = np.asarray(pallas_tpu.write_diag_blocks(out, W))
        np.testing.assert_array_equal(got, want)

    def test_misaligned_falls_back_to_dus(self):
        from capital_tpu.ops import pallas_tpu

        rng = np.random.default_rng(1)
        out = jnp.asarray(rng.standard_normal((192, 192)).astype(np.float32))
        W = jnp.asarray(rng.standard_normal((3, 64, 64)).astype(np.float32))
        want = np.asarray(out).copy()
        for i in range(3):
            want[i * 64:(i + 1) * 64, i * 64:(i + 1) * 64] = np.asarray(W[i])
        got = np.asarray(pallas_tpu.write_diag_blocks(out, W))
        np.testing.assert_array_equal(got, want)

    def test_dtype_cast_on_write(self):
        from capital_tpu.ops import pallas_tpu

        out = jnp.zeros((256, 256), jnp.bfloat16)
        W = jnp.ones((2, 128, 128), jnp.float32) * 1.5
        got = np.asarray(pallas_tpu.write_diag_blocks(out, W), np.float32)
        assert got[0, 0] == 1.5 and got[255, 255] == 1.5 and got[0, 200] == 0.0
