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
from capital_tpu.ops import batched_small, pallas_tpu, update_small
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
        "fused_sharded/full": {"builds": 1, "rows": 8192, "g": 8, "bm": 1024}}
    for kernel in ("CQR.gram.gram", "CQR.fused.scale_gram", "CQR.formR.scale"):
        assert kernel in txt, kernel
    assert len(re.findall(r"all-reduce(?:-start)?\(", txt)) == 2


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
