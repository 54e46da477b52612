"""A run with the timed path broken underneath reads `correct` false: once
for each fault the cell can have.  Small copies of the cells, on the CPU,
past the harness's look for a chip; a sound run of each reads true."""

import pytest

from conftest import run_cell


def _cholinv_state_unchanged(monkeypatch):
    from capital_tpu.models import cholesky

    monkeypatch.setattr(cholesky, "factor",
                        lambda grid, a, cfg, out_buffers: tuple(out_buffers))


def _cholinv_answer_altered(monkeypatch):
    from capital_tpu.models import cholesky

    real = cholesky.factor

    def factor(grid, a, cfg, out_buffers):
        R, Rinv = real(grid, a, cfg, out_buffers=out_buffers)
        return R * 1.05, Rinv

    monkeypatch.setattr(cholesky, "factor", factor)


def _cacqr_no_exchange(monkeypatch):
    """Each chip runs CholeskyQR2 on its own rows with its own Gram: the
    all-reduce between chips is left out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from capital_tpu.models import qr

    def one(x):
        g = jnp.matmul(x.T, x, precision="highest")
        r = jnp.linalg.cholesky(g).T
        q = jax.lax.linalg.triangular_solve(r, x, left_side=False,
                                            lower=False)
        return q, r

    def factor(grid, A, cfg):
        rows = P(("x", "y", "z"), None)

        def body(a):
            q1, r1 = one(a.astype(jnp.float32))
            q2, r2 = one(q1)
            return q2.astype(a.dtype), (r2 @ r1).astype(a.dtype)

        return jax.shard_map(body, mesh=grid.mesh, in_specs=rows,
                             out_specs=(rows, P()), check_vma=False)(A)

    monkeypatch.setattr(qr, "factor", factor)


def _cacqr_answer_altered(monkeypatch):
    from capital_tpu.models import qr

    real = qr.factor

    def factor(grid, A, cfg):
        Q, R = real(grid, A, cfg)
        return Q * 1.05, R

    monkeypatch.setattr(qr, "factor", factor)


def _serve_batch(monkeypatch, fault):
    from capital_tpu.serve import api

    real = api.batched

    def batched(op, *a, **k):
        fn = real(op, *a, **k)

        def run(A, B):
            X, info = fn(A, B)
            return fault(X), info

        return run

    monkeypatch.setattr(api, "batched", batched)


def _serve_answer_altered(monkeypatch):
    _serve_batch(monkeypatch, lambda X: X * 1.001)


def _serve_half_batch_left_out(monkeypatch):
    _serve_batch(monkeypatch, lambda X: X.at[1::2].set(0))


FAULTS = [
    ("cholinv.tiny", _cholinv_state_unchanged),
    ("cholinv.tiny", _cholinv_answer_altered),
    ("cacqr.tiny.x4", _cacqr_no_exchange),
    ("cacqr.tiny.x4", _cacqr_answer_altered),
    ("serve.tiny", _serve_answer_altered),
    ("serve.tiny", _serve_half_batch_left_out),
]


@pytest.mark.parametrize("cell", ["cholinv.tiny", "cacqr.tiny.x4",
                                  "serve.tiny"])
def test_sound_run_is_correct(cell, tiny):
    line = run_cell(tiny, cell, seconds=2.0)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("cell,plant", FAULTS,
                         ids=[f.__name__.lstrip("_") for _, f in FAULTS])
def test_fault_reads_incorrect(cell, plant, tiny, monkeypatch):
    plant(monkeypatch)
    line = run_cell(tiny, cell, seconds=2.0)
    assert not line["correct"], line["checks"]
