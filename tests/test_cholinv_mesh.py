"""cholinv on a 2x2 face, as the benchmark's mesh cell runs it: the cell's
configuration (its mode, balance and in-place protocol) against a NumPy
float64 Cholesky on four of the virtual CPU devices, the route counter of
the trmm/syrk sites for each schedule, the tile-cyclic syrk's per-shard
kernel against NumPy, and the mode rule."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.models import cholesky
from capital_tpu.obs import spans
from capital_tpu.parallel import summa
from capital_tpu.parallel.summa import SyrkArgs, TrmmArgs
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import rand48, tracing

CELL = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "configs", "cholinv-mesh.json")


@pytest.fixture
def routes(monkeypatch):
    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "SUMMA_ROUTES", fresh)
    return fresh


def _put(g, x):
    return jax.device_put(jnp.asarray(x), g.face_sharding())


def _spd(n, key):
    G = rand48.random(n, n, key=key)
    return G @ G.T / n + 3.0 * np.eye(n)


def _e4m3(x):
    """x rounded to float8 e4m3 after a power-of-two scale to its range."""
    import ml_dtypes

    s = 2.0 ** np.floor(np.log2(448.0 / np.abs(x).max()))
    return (x * s).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) / s


def _relgap(x, ref):
    return np.linalg.norm(np.asarray(x, np.float64) - ref) / np.linalg.norm(ref)


#: the bf16 operand alone moves NumPy's factor of these operands by
#: 3.3e-3 (n = 1024) and 4.5e-3 (2048); the float8 e4m3 operand (3 mantissa
#: bits) by 3.8e-2 and 5.0e-2
TOL = 1e-2


@pytest.mark.parametrize("n", [1024, 2048])
def test_the_cells_schedule_against_float64(grid2x2x1, n):
    """The cell's configuration, bc 128, bf16, in place into carried
    buffers: R and R⁻¹ within TOL of NumPy's float64 Cholesky, which
    NumPy's factor of the float8-rounded operand misses."""
    cell = json.load(open(CELL))
    g = grid2x2x1
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode=cell["mode"],
                                 balance=cell["balance"], precision=None,
                                 schur_in_place=True)
    A = _spd(n, key=n)
    R0 = np.linalg.cholesky(A).T
    Ri0 = np.linalg.inv(R0)
    bufs = jax.jit(lambda: cholesky.factor_buffers(g, n, jnp.bfloat16, cfg),
                   out_shardings=(g.face_sharding(),) * 2)()
    R, Ri = jax.jit(lambda a, rp, rip: cholesky.factor(
        g, a, cfg, out_buffers=(rp, rip)), donate_argnums=(1, 2))(
        _put(g, A.astype(jnp.bfloat16)), *bufs)
    assert _relgap(R, R0) < TOL
    assert _relgap(Ri, Ri0) < TOL
    assert _relgap(np.linalg.cholesky(_e4m3(A)).T, R0) > TOL


@pytest.mark.parametrize("balance", ["tile_cyclic", "tile_cyclic_persistent"])
@pytest.mark.parametrize("uplo", ["U", "L"])
def test_the_cyclic_syrk_kernel_per_shard(grid2x2x1, balance, uplo):
    """At 128-aligned tiles the tile-cyclic syrk runs one tri_matmul per
    shard with whole diagonal tiles (a shard's diagonal tile can be wholly
    live); the symmetrized update matches NumPy."""
    g, t, n = grid2x2x1, 128, 512
    A0 = rand48.random(n, n, key=91)
    kw = {}
    A = A0
    if balance == "tile_cyclic_persistent":
        perm, inv = summa.tile_cyclic_perm(n, 2, t)
        A = A0[perm][:, perm]
        kw = {"cyclic_tile": t}
    with tracing.Recorder() as rec:
        res = jax.jit(lambda a: summa.syrk(
            g, a, args=SyrkArgs(trans=True, uplo=uplo, alpha=0.5),
            mode="explicit", balance=balance, **kw))(_put(g, A))
    assert "explicit::cyclic_out_kernel" in rec.stats, sorted(rec.stats)
    got = np.asarray(res)
    if balance == "tile_cyclic_persistent":
        got = got[inv][:, inv]
    np.testing.assert_allclose(got, 0.5 * A0.T @ A0, atol=1e-11)


@pytest.mark.parametrize("mode,balance,want", [
    ("xla", "block", {"trmm/xla/gspmd", "syrk/xla/gspmd"}),
    ("explicit", "block", {"trmm/explicit_block/live_tiles",
                           "syrk/explicit_block/dense"}),
    ("explicit", "tile_cyclic", {"trmm/explicit_cyclic/dense",
                                 "syrk/explicit_cyclic/live_tiles"}),
    ("explicit", "tile_cyclic_persistent", {
        "trmm/explicit_persistent/live_tiles",
        "syrk/explicit_persistent/live_tiles"}),
])
def test_routes_of_each_schedule(grid2x2x1, routes, mode, balance, want):
    """Each trmm and syrk site counts `<op>/<schedule>/<local>` with the
    product's M, K, N and the grid's dx, dy, c."""
    g, n = grid2x2x1, 512
    kw = ({"cyclic_tile": 128} if balance == "tile_cyclic_persistent"
          else {})
    T = _put(g, np.triu(rand48.random(n, n, key=5)) + 4 * np.eye(n))
    B = _put(g, rand48.random(n, n, key=6))
    jax.eval_shape(lambda t, b: summa.trmm(
        g, t, b, TrmmArgs(side="L", uplo="U"), mode=mode, balance=balance,
        **kw), T, B)
    jax.eval_shape(lambda b: summa.syrk(
        g, b, args=SyrkArgs(trans=True), mode=mode, balance=balance, **kw), B)
    snap = routes.snapshot()
    assert set(snap) == want
    for tags in snap.values():
        assert (tags["builds"], tags["M"], tags["K"], tags["N"], tags["dx"],
                tags["dy"], tags["c"]) == (1, n, n, n, 2, 2, 1)


def test_one_device_routes_are_the_kernels(routes):
    g = Grid.square(c=1, devices=jax.devices()[:1])
    A = jnp.asarray(rand48.random(256, 256, key=7), jnp.float32)
    jax.eval_shape(lambda a: summa.trmm(
        g, a, a, TrmmArgs(side="R", uplo="U"), mode="pallas"), A)
    jax.eval_shape(lambda a: summa.syrk(
        g, a, a, SyrkArgs(trans=True, beta=1.0), mode="explicit",
        a_view=(0, 0, 128, 256), c_view=(0, 0, 256, 256)), A)
    snap = routes.snapshot()
    assert set(snap) == {"trmm/pallas/live_tiles",
                         "syrk/explicit_block/live_tiles"}
    assert [snap["syrk/explicit_block/live_tiles"][k]
            for k in ("M", "K", "N", "dx", "c")] == [256, 128, 256, 1, 1]


def test_the_mode_rule_by_platform_and_devices():
    """'auto' is the kernels on one TPU and xla on a TPU mesh and on any
    CPU grid (Pallas means the interpreter there); the mesh cell names
    its schedule, which passes through."""
    def grid(platform, devices):
        return types.SimpleNamespace(platform=platform, num_devices=devices)

    assert summa.resolve_mode("auto", grid("tpu", 4)) == "xla"
    assert summa.resolve_mode("explicit", grid("tpu", 4)) == "explicit"
    assert summa.resolve_mode("auto", grid("tpu", 1)) == "pallas"
    assert summa.resolve_mode("auto", grid("cpu", 4)) == "xla"
    assert summa.resolve_mode("xla", grid("tpu", 4)) == "xla"


@pytest.mark.parametrize("m,d,t", [(64, 2, 8), (96, 2, 16), (128, 4, 8)])
def test_cyclic_permute_is_the_index_gather(m, d, t):
    """The reshape-and-transpose shuffle equals the gather by
    tile_cyclic_perm, forward and back, on either axis."""
    X = np.arange(m * m, dtype=np.float64).reshape(m, m)
    perm, inv = summa.tile_cyclic_perm(m, d, t)
    np.testing.assert_array_equal(summa.cyclic_permute(X, d, t),
                                  X[perm][:, perm])
    np.testing.assert_array_equal(
        summa.cyclic_permute(X, d, t, inverse=True), X[inv][:, inv])
    np.testing.assert_array_equal(summa.cyclic_permute(X, d, t, (1,)),
                                  X[:, perm])
    np.testing.assert_array_equal(
        summa.cyclic_permute(X, d, t, (0,), inverse=True), X[inv])
