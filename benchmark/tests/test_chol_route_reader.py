"""The reader of ``xla_chol_sites.factor``: the builds of the XLA
factor-and-invert path in the program's ``CHOL_ROUTES`` counter, 0 where
every site took the Pallas kernel, and None where no site was counted or
the program keeps no such counter."""

import pytest

import common


@pytest.fixture
def routes(monkeypatch):
    from capital_tpu.obs import spans

    fresh = spans.RouteCounter()
    monkeypatch.setattr(spans, "CHOL_ROUTES", fresh)
    return fresh


def _read():
    return common.Catalog().reader("xla_chol_sites.factor").read(None)


def test_counts_the_xla_sites(routes):
    assert _read() is None  # no site traced
    routes.take("potrf_trtri/pallas", n=1024)
    routes.take("potrf_trtri/pallas", n=1024)
    assert _read() == 0
    routes.take("potrf_trtri/xla", n=384)
    routes.take("potrf_trtri/xla", n=384)
    assert _read() == 2


def test_without_the_counter(monkeypatch):
    """An older program keeps no such counter: None, and nothing raises."""
    from capital_tpu.obs import spans

    monkeypatch.delattr(spans, "CHOL_ROUTES", raising=False)
    assert _read() is None
