"""The dense path's rules, each in the layer that owns it: the SUMMA mode
and precision defaults (parallel/summa), the largest square grid of a
device set (parallel/topology), the residual gate and the operands it is
checked on (utils/residual), and the benchmark harness's coupling rule
(bench/harness)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from capital_tpu.parallel import summa
from capital_tpu.parallel.topology import Grid
from capital_tpu.utils import residual


@pytest.mark.parametrize("mode", ["explicit", "xla", "pallas"])
def test_resolve_mode_passes_a_named_mode_through(mode):
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    assert summa.resolve_mode(mode, grid) == mode


def test_resolve_mode_auto_on_one_cpu_device_is_xla():
    # pallas off the TPU is the interpreter: 'auto' keeps the CPU on xla
    grid = Grid.square(c=1, devices=jax.devices()[:1])
    assert summa.resolve_mode("auto", grid) == "xla"


def test_resolve_mode_auto_on_a_four_device_grid_is_xla(grid2x2x1):
    assert grid2x2x1.num_devices == 4
    assert summa.resolve_mode("auto", grid2x2x1) == "xla"


@pytest.mark.parametrize(
    "dtype, want",
    [(jnp.bfloat16, None), (jnp.float32, "highest"), (jnp.float64, "highest")],
)
def test_default_precision(dtype, want):
    assert summa.default_precision(dtype) == want


@pytest.mark.parametrize(
    "ndev, c, shape",
    [(1, 1, (1, 1, 1)), (4, 1, (2, 2, 1)), (8, 1, (2, 2, 2)),
     (8, 2, (2, 2, 2)), (6, 1, (2, 2, 1))],
)
def test_largest_square(ndev, c, shape):
    grid = Grid.largest_square(jax.devices()[:ndev], c=c)
    assert (grid.dx, grid.dy, grid.c) == shape


@pytest.mark.parametrize(
    "dtype, tol",
    [(jnp.bfloat16, 5e-2), (jnp.float32, 5e-5), (jnp.float64, 1e-13)],
)
def test_tolerance_by_dtype(dtype, tol):
    assert residual.tolerance(dtype) == tol


def test_spd_operand_is_symmetric_and_well_conditioned():
    A = np.asarray(residual.spd_operand(64, jnp.float64, seed=3))
    np.testing.assert_allclose(A, A.T, rtol=0, atol=1e-6)
    w = np.linalg.eigvalsh(A)
    assert w.min() > 0.5 and w.max() < 6.0  # 3I ± the Wigner edge at 2


def test_tri_operand_is_lower_and_well_conditioned():
    L = np.asarray(residual.tri_operand(64, jnp.float64))
    np.testing.assert_array_equal(L, np.tril(L))
    assert np.linalg.cond(L) < 4.0


def test_pallas_coupled_is_off_in_xla_mode():
    from capital_tpu.bench import harness

    grid = Grid.square(c=1, devices=jax.devices()[:1])
    assert not harness.pallas_coupled(grid, 4096, 512, "xla", jnp.float32)
