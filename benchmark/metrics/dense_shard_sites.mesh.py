"""trmm and syrk sites whose per-shard product multiplies part of the dead
triangle, from the program's own route counter
(capital_tpu/obs/spans.py: ``SUMMA_ROUTES``, which parallel/summa.py counts
as each site is traced under ``<op>/<schedule>/<local>``): the builds of
the routes whose ``<local>`` is ``dense`` (XLA dots in each shard, where a
Pallas kernel would skip the dead tiles).  It reads 0 when every site took
a live-tile kernel or GSPMD.  A program that counted no site, or keeps no
such counter, gives None."""

SUFFIX = "/dense"


def read(r):
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return None
    routes = getattr(spans, "SUMMA_ROUTES", None)
    if routes is None:
        return None
    snap = routes.snapshot()
    if not snap:
        return None
    return sum(v["builds"] for k, v in snap.items() if k.endswith(SUFFIX))
