"""The Pallas kernel cache (ops/pallas_tpu._kernel_cache).

View offsets reach the kernels as a runtime int32 operand, so every call
with the same sizes, blocks and flags is one kernel, traced and lowered
once:

* a view at any aligned offset gives the result, bitwise, that the same
  call on the materialized window gives (offsets folded to zero), and the
  jnp product of the window (integer-valued operands make every sum exact);
* the in-place forms write only their window and keep the rest;
* cholinv builds four kernels per recursion level plus two leaf kernels,
  however many nodes call them, and a second trace builds nothing;
* a kernel shared by two phases is built once under each, and keeps each
  phase's name.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.lint import program
from capital_tpu.models import cholesky
from capital_tpu.obs import spans
from capital_tpu.ops import pallas_tpu
from capital_tpu.parallel.topology import Grid

W = 512  # window size
P = 1024  # buffer size
BLOCKS = (128, 128, 128)


def _ints(shape, seed):
    """Small integers as f32: products and sums stay exact."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-3, 4, size=shape).astype(np.float32))


def _win(x, r0, c0, rows=W, cols=W):
    return x[r0:r0 + rows, c0:c0 + cols]


def _op(x, uplo, trans):
    x = np.asarray(x, np.float64)
    if uplo == "U":
        x = np.triu(x)
    elif uplo == "L":
        x = np.tril(x)
    return x.T if trans else x


def _count():
    return spans.KERNELS.snapshot()


# every branch of tri_matmul: (flags, offsets of A's and B's views)
_BRANCHES = {
    "dense": dict(),
    "dense_trans": dict(a_trans=True, b_trans=True),
    "trmm_a": dict(a_uplo="U", a_trans=True),
    "trmm_b": dict(b_uplo="U"),
    "syrk": dict(a_trans=True, b_trans=False, out_uplo="U"),
}
_OFFSETS = [((0, 0), (0, 512)), ((128, 384), (512, 256)),
            ((512, 512), (256, 128))]


@pytest.mark.parametrize("branch", sorted(_BRANCHES))
@pytest.mark.parametrize("offs", _OFFSETS, ids=lambda o: f"{o[0]}-{o[1]}")
def test_view_offsets_equal_materialized_window(branch, offs):
    flags = _BRANCHES[branch]
    (ar, ac), (br, bc) = offs
    A, B = _ints((P, P), 1), _ints((P, P), 2)
    got = pallas_tpu.tri_matmul(A, B, blocks=BLOCKS, a_view=(ar, ac, W, W),
                                b_view=(br, bc, W, W), **flags)
    Aw, Bw = _win(A, ar, ac), _win(B, br, bc)
    folded = pallas_tpu.tri_matmul(Aw, Bw, blocks=BLOCKS, **flags)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(folded))
    ref = (_op(Aw, flags.get("a_uplo"), flags.get("a_trans", False))
           @ _op(Bw, flags.get("b_uplo"), flags.get("b_trans", False)))
    if "out_uplo" in flags:
        ref = np.triu(ref)
    np.testing.assert_array_equal(np.asarray(got, np.float64), ref)


@pytest.mark.parametrize("branch", ["dense", "trmm_a", "trmm_b"])
@pytest.mark.parametrize("form", ["out", "out_is_a", "out_is_b"])
def test_in_place_out_writes_only_its_window(branch, form):
    flags = _BRANCHES[branch]
    A, B = _ints((P, P), 3), _ints((P, P), 4)
    a_view, b_view = (0, 0, W, W), (0, W, W, W)
    out_off = (W, W)  # disjoint from both read windows
    ref_win = np.asarray(pallas_tpu.tri_matmul(
        _win(A, 0, 0), _win(B, 0, W), blocks=BLOCKS, **flags))
    if form == "out":
        buf = _ints((P, P), 5)
    else:
        buf = A if form == "out_is_a" else B
    before = np.asarray(buf)
    got = np.asarray(jax.jit(
        lambda a, b, o: pallas_tpu.tri_matmul(
            a if form != "out_is_a" else o, b if form != "out_is_b" else o,
            blocks=BLOCKS, a_view=a_view, b_view=b_view, out=o,
            out_off=out_off, **flags))(A, B, buf))
    np.testing.assert_array_equal(_win(got, *out_off), ref_win)
    keep = np.ones((P, P), bool)
    keep[W:, W:] = False
    np.testing.assert_array_equal(got[keep], before[keep])


@pytest.mark.parametrize("coff", [(0, 0), (512, 512), (256, 384)])
def test_syrk_read_modify_write_at_offsets(coff):
    A, C = _ints((P, P), 6), _ints((P, P), 7)
    a_view = (0, 256, 256, W)
    c_view = (*coff, W, W)
    ref = np.asarray(pallas_tpu.tri_matmul(
        _win(A, 0, 256, 256, W), _win(A, 0, 256, 256, W), a_trans=True,
        out_uplo="U", alpha=-1.0, blocks=BLOCKS, c=_win(C, *coff),
        beta=1.0))
    before = np.asarray(C)
    got = np.asarray(jax.jit(lambda a, c: pallas_tpu.tri_matmul(
        a, a, a_trans=True, out_uplo="U", alpha=-1.0, blocks=BLOCKS,
        a_view=a_view, b_view=a_view, c=c, c_view=c_view, beta=1.0,
        out=c, out_off=coff))(A, C))
    win = _win(got, *coff)
    np.testing.assert_array_equal(np.triu(win), np.triu(ref))
    exact = (np.triu(np.asarray(_win(C, *coff), np.float64))
             - np.triu(_op(_win(A, 0, 256, 256, W), None, True)
                       @ _op(_win(A, 0, 256, 256, W), None, False)))
    np.testing.assert_array_equal(np.triu(win), exact)
    keep = np.ones((P, P), bool)
    keep[coff[0]:coff[0] + W, coff[1]:coff[1] + W] = False
    np.testing.assert_array_equal(got[keep], before[keep])


@pytest.mark.parametrize("view", [(0, 512), (384, 128), (512, 0)])
@pytest.mark.parametrize("form", ["fresh", "out", "out_is_x"])
def test_transpose_view_offsets(view, form):
    X = _ints((P, P), 8)
    r0, c0 = view
    want = np.triu(np.asarray(_win(X, r0, c0, W, 256)).T)
    if form == "fresh":
        got = pallas_tpu.transpose(X, in_view=(r0, c0, W, 256),
                                   out_uplo="U")
        np.testing.assert_array_equal(np.asarray(got), want)
        return
    dest = (768, 512)  # disjoint from every read window
    buf = _ints((P, P), 9) if form == "out" else X
    before = np.asarray(buf)
    got = np.asarray(jax.jit(lambda x, o: pallas_tpu.transpose(
        x if form == "out" else o, in_view=(r0, c0, W, 256), out_uplo="U",
        out=o, out_off=dest))(X, buf))
    np.testing.assert_array_equal(_win(got, *dest, 256, W), want)
    keep = np.ones((P, P), bool)
    keep[dest[0]:dest[0] + 256, dest[1]:dest[1] + W] = False
    np.testing.assert_array_equal(got[keep], before[keep])


@pytest.mark.parametrize("dest", [0, 256, 768])
def test_transpose_pair_dest_offsets(dest):
    n = 256
    L, Li = _ints((n, n), 10), _ints((n, n), 11)
    Rp, RIp = _ints((P, P), 12), _ints((P, P), 13)
    seq = jax.jit(lambda l, li, r, ri: (
        pallas_tpu.transpose(l, out_uplo="U", out=r, out_off=(dest, dest)),
        pallas_tpu.transpose(li, out_uplo="U", out=ri,
                             out_off=(dest, dest))))(L, Li, Rp, RIp)
    pair = jax.jit(lambda l, li, r, ri: pallas_tpu.transpose_pair(
        l, li, r, ri, dest=dest))(L, Li, Rp, RIp)
    for s, p, x, buf in zip(seq, pair, (L, Li), (Rp, RIp)):
        s, p = np.asarray(s), np.asarray(p)
        np.testing.assert_array_equal(p, s)
        np.testing.assert_array_equal(_win(p, dest, dest, n, n),
                                      np.triu(np.asarray(x).T))
        keep = np.ones((P, P), bool)
        keep[dest:dest + n, dest:dest + n] = False
        np.testing.assert_array_equal(p[keep], np.asarray(buf)[keep])


def test_unaligned_view_takes_the_materializing_fallback():
    A, B = _ints((P, P), 14), _ints((P, P), 15)
    a_view, b_view = (64, 0, W, W), (0, 192, W, W)  # 64 fits no block
    want = (_op(_win(A, 64, 0), "U", True) @ _op(_win(B, 0, 192), None, False))
    jx = jax.make_jaxpr(lambda a, b: pallas_tpu.tri_matmul(
        a, b, a_uplo="U", a_trans=True, a_view=a_view, b_view=b_view))(A, B)
    assert any(e.primitive.name == "slice" for e, _ in
               program.iter_eqns(jx.jaxpr))
    got = pallas_tpu.tri_matmul(A, B, a_uplo="U", a_trans=True,
                                a_view=a_view, b_view=b_view)
    np.testing.assert_array_equal(np.asarray(got, np.float64), want)


# ---------------------------------------------------------------------------
# cholinv: one kernel per level and kind
# ---------------------------------------------------------------------------


def _kernel_sites(jaxpr, out):
    """(call-site phase, kernel name) of every pallas_call in a jaxpr, one
    per call site: a kernel's jaxpr shared by several sites counts at each."""
    for eqn, phase in program.iter_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            out.append((phase, eqn.params["name"]))
    return out


def _step():
    g = Grid.square(c=1, devices=jax.devices()[:1])
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas",
                                 schur_in_place=True)

    def step(a, rp, rip):
        return cholesky.factor(g, a, cfg, out_buffers=(rp, rip))

    return step


def test_cholinv_builds_one_kernel_per_level_and_kind():
    n, bc = 2048, 128
    levels = 4  # node sizes 2048, 1024, 512, 256; 16 leaves of 128
    s = jax.ShapeDtypeStruct((n, n), jnp.float32)
    jax.clear_caches()
    c0 = _count()
    traced = jax.jit(_step()).trace(s, s, s)
    c1 = _count()
    sites = _kernel_sites(traced.jaxpr.jaxpr, [])
    leaves = n // bc
    assert len(sites) == 4 * (leaves - 1) + 2 * leaves
    assert c1["calls"] - c0["calls"] == len(sites)
    assert c1["built"] - c0["built"] == 4 * levels + 2
    # a second trace of the same step builds nothing
    jax.jit(_step()).trace(s, s, s)
    c2 = _count()
    assert c2["calls"] - c1["calls"] == len(sites)
    assert c2["built"] == c1["built"]


def test_a_kernel_shared_by_two_phases_keeps_each_name():
    s = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    traced = jax.jit(_step()).trace(s, s, s)
    sites = _kernel_sites(traced.jaxpr.jaxpr, [])
    for phase, name in sites:
        assert name.rsplit(".", 1)[0] == phase.replace("::", "."), \
            (phase, name)
    names = {name for _, name in sites}
    assert {"CI.trsm.trmm_left", "CI.inv.trmm_left"} <= names
    text = traced.lower().as_text(debug_info=True)
    assert "CI.trsm.trmm_left" in text and "CI.inv.trmm_left" in text


def test_same_kernel_under_two_phases_is_built_under_each():
    from capital_tpu.utils import tracing

    def two(a, b):
        with tracing.scope("CI::trsm"):
            x = pallas_tpu.tri_matmul(a, b, a_uplo="U", blocks=BLOCKS)
        with tracing.scope("CI::inv"):
            y = pallas_tpu.tri_matmul(a, b, a_uplo="U", blocks=BLOCKS)
        return x, y

    s = jax.ShapeDtypeStruct((W, W), jnp.float32)
    sites = _kernel_sites(jax.make_jaxpr(two)(s, s).jaxpr, [])
    assert sites == [("CI::trsm", "CI.trsm.trmm_left"),
                     ("CI::inv", "CI.inv.trmm_left")]
