"""Hermeticity of the multi-chip dryrun path (round-1 MULTICHIP gate).

The round-1 gate failed for two distinct reasons (VERDICT r1, weak #1):
  (a) dryrun operands were created with bare ``jnp.asarray``, committing them
      to the *process default* backend (a TPU in the bench environment) even
      though the mesh had fallen back to CPU devices — dying at device_put
      with a libtpu client/terminal mismatch;
  (b) Pallas interpret-mode selection keyed off ``jax.default_backend()``
      instead of the platform of the mesh's devices, so a CPU mesh in a
      TPU-backed process picked the Mosaic lowering and died with "Only
      interpret mode is supported on CPU backend" (the base case reaches
      pallas_tpu.transpose via lapack.potrf_trtri_upper on every grid).

These tests simulate the mixed environment on the CPU-only rig by
monkeypatching ``pallas_tpu._default_backend`` to report 'tpu' while every
mesh is CPU: any kernel-dispatch path not threaded through the Grid's
platform scope then tries the Mosaic path and fails loudly.  The last test
runs the driver's actual ``dryrun_multichip(8)`` end to end.
"""

import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from capital_tpu.models import cholesky, inverse, qr
from capital_tpu.ops import pallas_tpu
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import residual


@pytest.fixture
def tpu_default_backend(monkeypatch):
    """Pretend the process default backend is a TPU (the bench environment)
    while all devices in play are CPU."""
    monkeypatch.setattr(pallas_tpu, "_default_backend", lambda: "tpu")


def _spd(n: int, dtype=np.float32) -> np.ndarray:
    rng = np.random.default_rng(7)
    M = rng.standard_normal((n, n)).astype(dtype)
    return M @ M.T + n * np.eye(n, dtype=dtype)


def test_interpret_keys_off_mesh_platform(tpu_default_backend):
    # without a scope the (simulated) TPU default backend selects Mosaic...
    assert pallas_tpu._interpret_default() is False
    # ...but inside a CPU grid's scope the interpreter must win
    with pallas_tpu.device_scope(jax.devices("cpu")[0]):
        assert pallas_tpu._interpret_default() is True
        # and the tile budget must follow the scope too (never touching
        # jax.devices('tpu'), which does not exist on this rig)
        assert pallas_tpu._device_budget() == (512, None)
    assert Grid.square(c=1, devices=jax.devices("cpu")[:1]).platform == "cpu"


def test_single_device_pallas_factor_with_tpu_default(tpu_default_backend):
    # the flagship config family (pallas mode: live-tile kernels, views,
    # aliased in-place writes) on a CPU device while the default backend
    # claims TPU — every pallas call must resolve interpret via the grid
    grid = Grid.square(c=1, devices=jax.devices("cpu")[:1])
    A = jax.device_put(_spd(256), grid.face_sharding())
    cfg = cholesky.CholinvConfig(base_case_dim=128, mode="pallas")
    R, Rinv = jax.jit(lambda a: cholesky.factor(grid, a, cfg))(A)
    jax.block_until_ready((R, Rinv))
    assert float(residual.cholesky_residual(A, R)) < 1e-4
    assert float(residual.cholesky_inverse_residual(R, Rinv)) < 1e-4


def test_multidevice_factor_with_tpu_default(tpu_default_backend):
    # multi-device grids reach pallas_tpu.transpose through the base case's
    # lapack.potrf_trtri_upper — the exact crash site of round-1 bug (b)
    grid = Grid.square(c=1, devices=jax.devices("cpu")[:4])
    A = jax.device_put(_spd(128), grid.face_sharding())
    cfg = cholesky.CholinvConfig(base_case_dim=32, mode="explicit")
    R, Rinv = jax.jit(lambda a: cholesky.factor(grid, a, cfg))(A)
    jax.block_until_ready((R, Rinv))
    assert float(residual.cholesky_residual(A, R)) < 1e-4


def test_qr_and_rectri_scoped_with_tpu_default(tpu_default_backend):
    grid = Grid.flat(jax.devices("cpu"))
    rng = np.random.default_rng(3)
    X = jax.device_put(
        rng.standard_normal((128, 16)).astype(np.float32), grid.rows_sharding()
    )
    Q, R = jax.jit(
        lambda x: qr.factor(grid, x, qr.CacqrConfig(num_iter=2, regime="1d"))
    )(X)
    jax.block_until_ready((Q, R))
    assert float(residual.qr_orthogonality(Q)) < 1e-4

    g1 = Grid.square(c=1, devices=jax.devices("cpu")[:1])
    T = jax.device_put(
        np.tril(rng.standard_normal((64, 64)).astype(np.float32))
        + 64 * np.eye(64, dtype=np.float32),
        g1.face_sharding(),
    )
    Tinv = jax.jit(
        lambda t: inverse.rectri(g1, t, "L", inverse.RectriConfig(base_case_dim=32))
    )(T)
    assert float(residual.inverse_residual(T, Tinv)) < 1e-4


def _load_graft_entry():
    path = pathlib.Path(__file__).resolve().parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("graft_entry_for_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dryrun_hermetic_no_default_backend_execution(
    tpu_default_backend, monkeypatch
):
    """Round-4 MULTICHIP regression: rc=1 because eager ops in the dryrun
    (the residual-gate block's mask constants) dispatched on the *process
    default* backend, which was a TPU with a libtpu client/terminal version
    skew.  Simulate exactly that: default-backend *resolution* for execution
    raises (as the skewed TPU client did), while explicit-platform lookups
    and the device listing still work (they did in the real environment —
    ``jax.devices()`` returned the TPU fine; only executing on it died).
    The dryrun must survive because ``jax.default_device`` pins every
    uncommitted eager op to the mesh's own devices."""
    import jax._src.xla_bridge as xb

    mod = _load_graft_entry()
    cpu_devices = jax.devices("cpu")
    real_get_backend = xb.get_backend

    def broken_default_backend(platform=None):
        if platform is None:
            raise RuntimeError(
                "SIMULATED FAILED_PRECONDITION: libtpu version mismatch "
                "(process-default backend touched by the dryrun)"
            )
        return real_get_backend(platform)

    # the dryrun's own device listing is allowed (it worked in the real
    # failure env); execution-time default-backend resolution is not
    monkeypatch.setattr(mod.jax, "devices", lambda *a: cpu_devices)
    monkeypatch.setattr(xb, "get_backend", broken_default_backend)
    mod.dryrun_multichip(8)


def test_dryrun_multichip_runs_end_to_end(tpu_default_backend):
    # the driver imports __graft_entry__ and calls dryrun_multichip(N)
    # directly (the __main__ platform guard never runs) — do the same,
    # under the simulated TPU default backend so every kernel-dispatch
    # decision in the dryrun call tree is exercised in the mixed environment
    _load_graft_entry().dryrun_multichip(8)
