"""Main-path programs compile for a described TPU v5e (no chip needed).

Interpret mode hides what Mosaic refuses (block layouts, scalar VMEM
stores, VMEM budgets) and a CPU mesh hides what XLA:TPU refuses, so each
test lowers one main-path program at a real width against a v5e:2x2
topology description and asserts the compiled HLO carries
``tpu_custom_call`` — the Pallas kernels went through Mosaic, not the
interpreter.  Kernel dispatch follows the device the program is compiled
for: grid entry points read it from the Grid; the bare small-N kernels are
steered here with ``pallas_tpu.device_scope``.

The topology is described inside a module fixture (never at import or
collection): only one process may load libtpu at a time, and under
pytest-xdist every worker imports this file.  Keep these tests in this one
file so they share that fixture on one worker.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from capital_tpu.models import cholesky, qr
from capital_tpu.obs import spans
from capital_tpu.ops import batched_small, pallas_tpu, qr_fused, update_small
from capital_tpu.parallel.topology import Grid


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out of any cache
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def chip(topo):
    return topo.devices[0]


@pytest.fixture(scope="module")
def v4_chip(topo):
    """One chip of a described TPU v4, whose kind keeps Mosaic's own
    16 MiB of scoped VMEM (`pallas_tpu._TILE_BUDGET`)."""
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v4:2x2x1")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v4:2x2x1 topology can be described here: {e}")
    return t.devices[0]


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(chip))


def _mosaic_calls(fn, *args, scope=None) -> int:
    if scope is None:
        compiled = jax.jit(fn).lower(*args).compile()
    else:
        with pallas_tpu.device_scope(scope):
            compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


def test_cholinv_pallas_bf16(chip):
    g = Grid.square(c=1, devices=[chip])
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas")
    A = _sds(chip, (4096, 4096), jnp.bfloat16)
    assert _mosaic_calls(lambda a: cholesky.factor(g, a, cfg), A) > 0


def test_cholinv_fused_tail(chip):
    g = Grid.square(c=1, devices=[chip])
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas",
                                 tail_fuse_depth=1)
    A = _sds(chip, (1024, 1024), jnp.float32)
    compiled = jax.jit(lambda a: cholesky.factor(g, a, cfg)).lower(A).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt and "fused_tail" in txt


def test_cqr2_pallas(chip):
    g = Grid.square(c=1, devices=[chip])
    cfg = qr.CacqrConfig(num_iter=2, mode="pallas")
    A = _sds(chip, (65536, 512), jnp.float32)
    assert _mosaic_calls(lambda a: qr.factor(g, a, cfg), A) > 0


def test_cqr2_rows_over_four_chips(topo, monkeypatch):
    """The cacqr-tall route: the fused tall-pass kernels per shard inside
    shard_map on the four chips (g=8, plan "full"), and the two Gram
    all-reduces between them."""
    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "ROUTES", routes)
    g = Grid.flat(topo.devices[:4])
    cfg = qr.CacqrConfig(num_iter=2, regime="1d", mode="pallas")
    A = jax.ShapeDtypeStruct((4 * 8192, 1024), jnp.bfloat16,
                             sharding=g.rows_sharding())
    txt = jax.jit(lambda a: qr.factor(g, a, cfg)).lower(A).compile().as_text()
    assert routes.snapshot() == {
        "fused_sharded/full": {"builds": 1, "rows": 8192, "g": 8, "bm": 4096,
                               "bm_scale_gram": 1024}}
    for kernel in ("CQR.gram.gram", "CQR.fused.scale_gram", "CQR.formR.scale",
                   "CQR.chol.potrf_trtri"):
        assert kernel in txt, kernel
    assert len(re.findall(r"all-reduce(?:-start)?\(", txt)) == 2


@pytest.mark.parametrize("kernel,n,dtype,bm", [
    ("gram", 4096, jnp.bfloat16, 512),  # the 'split' tier's Gram
    ("scale_gram", 2048, jnp.float32, 512),  # the f32 passes' copies
])
def test_tall_pass_kernel_at_its_row_block(chip, kernel, n, dtype, bm):
    """The row block qr_fused.tall_bm picks near the edge of its VMEM model
    compiles, at the precision CacqrConfig passes ("highest")."""
    with pallas_tpu.device_scope(chip):
        assert qr_fused.tall_bm(kernel, 65536, n, dtype) == bm
    g = qr_fused.pick_g(n)
    fn = {
        "gram": lambda a, r: qr_fused.gram_blocked(a, g=g, precision="highest"),
        "scale_gram": lambda a, r: qr_fused.scale_gram(a, r, g=g,
                                                       precision="highest"),
    }[kernel]
    A, R = _sds(chip, (65536, n), dtype), _sds(chip, (n, n), dtype)
    assert _mosaic_calls(fn, A, R, scope=chip) == 1


@pytest.mark.parametrize("n,N", [(1024, None), (384, 3072)])
def test_factor_and_invert_kernel(chip, n, N):
    """The one-kernel factor and inverse at the Gram's 1024 and, in place
    in windows of larger bf16 buffers, at cholinv's 384 leaf."""
    if N is None:
        fn = pallas_tpu.potrf_trtri_upper
        args = (_sds(chip, (n, n), jnp.bfloat16),)
    else:
        def fn(buf, rp, rip):
            return pallas_tpu.potrf_trtri_upper(buf, off=n, n=n, Rp=rp,
                                                RIp=rip, dest=2 * n)
        args = tuple(_sds(chip, (N, N), jnp.bfloat16) for _ in range(3))
    assert _mosaic_calls(fn, *args, scope=chip) == 1


def test_dense_refined_solve(chip, monkeypatch):
    """refine.posv_dense, the mxp cell's program at a smaller n: the bf16
    factor on the Pallas kernels, A only read (no working copy of it), and
    the FP64-grade residual in its Pallas kernel (route refine/pallas),
    with no n² temporary beside R, R⁻¹ and the trailing windows."""
    from capital_tpu.robust import refine

    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "REFINE_ROUTES", routes)
    n = 4096  # at 2048 the temporaries do not show a working copy of A
    g = Grid.square(c=1, devices=[chip])
    compiled = jax.jit(lambda a, b: refine.posv_dense(g, a, b)).lower(
        _sds(chip, (n, n), jnp.bfloat16), _sds(chip, (n,), jnp.float32),
    ).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    assert not re.search(rf"bf16\[{n},{n}\]\S* copy\(", txt)
    assert list(routes.snapshot()) == ["refine/pallas"]
    assert routes.snapshot()["refine/pallas"]["n"] == n
    assert "IR.residual.residual" in txt
    # R, R⁻¹ and the trailing windows; a working copy of A adds 2 n²
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * n * n * 2


@pytest.mark.parametrize("k,route", [(2, "refine/pallas"),
                                     (8, "refine/xla_f64"),
                                     (64, "refine/xla_f64")])
def test_dense_refined_solve_columns(chip, monkeypatch, k, route):
    """posv_dense on a bf16 A with k right-hand sides, as the serve tier's
    oversize guaranteed posv sends them: up to pallas_tpu.RESID_MAX_K
    columns the residual kernel, more the XLA route; either compiles."""
    from capital_tpu.robust import refine

    routes = spans.RouteCounter()
    monkeypatch.setattr(spans, "REFINE_ROUTES", routes)
    n = 2048
    g = Grid.square(c=1, devices=[chip])
    txt = jax.jit(lambda a, b: refine.posv_dense(g, a, b)).lower(
        _sds(chip, (n, n), jnp.bfloat16), _sds(chip, (n, k), jnp.float32),
    ).compile().as_text()
    assert list(routes.snapshot()) == [route]
    assert ("IR.residual.residual" in txt) is (route == "refine/pallas")


@pytest.mark.parametrize("k", [1, 2])
def test_residual_kernel_fits_a_v4(v4_chip, k):
    """The residual kernel narrows its grid step to Mosaic's 16 MiB on a
    TPU kind with no raised VMEM limit (1024 × 4096 needs ~22 MiB)."""
    n = 4096
    with pallas_tpu.device_scope(v4_chip):
        assert pallas_tpu.residual_blocks(n, k)[1] < 4096
    b = _sds(v4_chip, (n, k), jnp.float32)
    assert _mosaic_calls(
        lambda a, b: pallas_tpu.residual_bf16(a, b, b, b, anorm=3.0),
        _sds(v4_chip, (n, n), jnp.bfloat16), b, scope=v4_chip) == 1


@pytest.mark.parametrize("op,m", [("posv", 64), ("lstsq", 128)])
def test_batched_small(chip, op, m):
    A = _sds(chip, (32, m, 64), jnp.float32)
    B = _sds(chip, (32, m, 1), jnp.float32)
    fn = getattr(batched_small, op)
    assert _mosaic_calls(fn, A, B, scope=chip) == 1


def test_update_small(chip):
    R = _sds(chip, (8, 128, 128), jnp.float32)
    V = _sds(chip, (8, 128, 4), jnp.float32)
    assert _mosaic_calls(
        lambda r, v: update_small.chol_update(r, v, impl="pallas"), R, V,
        scope=chip,
    ) == 1


def test_cholinv_mesh_cell(topo, monkeypatch):
    """The mesh cell's program on a described 2x2 face at n = 2048: the
    cell names the explicit schedule, while 'auto' stays xla on a TPU
    mesh; under the persistent
    tile-cyclic balance every trmm and syrk site runs a live-tile kernel
    per shard, the leaves are XLA's replicated pair, and the panels move
    by all-gathers."""
    import json

    from capital_tpu.parallel import summa

    cell = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "cholinv-mesh.json")))
    routes, chol = spans.RouteCounter(), spans.RouteCounter()
    monkeypatch.setattr(spans, "SUMMA_ROUTES", routes)
    monkeypatch.setattr(spans, "CHOL_ROUTES", chol)
    g = Grid.square(c=1, devices=topo.devices[:4])
    assert summa.resolve_mode("auto", g) == "xla"
    mode = summa.resolve_mode(cell["mode"], g)
    assert mode == "explicit"
    n = 2048
    cfg = cholesky.CholinvConfig(
        base_case_dim=cell["base_case_dim"], mode=mode,
        balance=cell["balance"], precision=None, schur_in_place=True)
    A = jax.ShapeDtypeStruct((n, n), jnp.bfloat16, sharding=g.face_sharding())
    txt = jax.jit(lambda a, rp, rip: cholesky.factor(
        g, a, cfg, out_buffers=(rp, rip)), donate_argnums=(1, 2)).lower(
        A, A, A).compile().as_text()
    assert set(routes.snapshot()) == {"trmm/explicit_persistent/live_tiles",
                                      "syrk/explicit_persistent/live_tiles"}
    assert chol.snapshot() == {"potrf_trtri/xla": {"builds": 4, "n": 512}}
    assert "tpu_custom_call" in txt
    assert re.search(r"all-gather(?:-start)?\(", txt)
