"""Factor-and-invert sites the program built on XLA's Cholesky and
triangular solve instead of its one Pallas kernel, from the program's own
counter (capital_tpu/obs/spans.py: ``CHOL_ROUTES``, which ops/lapack.py and
models/cholesky.py count as each site is traced): the builds of
``potrf_trtri/xla``.  It reads 0 when every site took the kernel
(``potrf_trtri/pallas``).  A program that counted no ``potrf_trtri/`` route,
or keeps no such counter, gives None."""

PREFIX = "potrf_trtri/"


def read(r):
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return None
    routes = getattr(spans, "CHOL_ROUTES", None)
    if routes is None:
        return None
    snap = routes.snapshot()
    if not any(k.startswith(PREFIX) for k in snap):
        return None
    return snap.get(PREFIX + "xla", {}).get("builds", 0)
