"""Builds of qr.factor in the process that took a route other than the fused
tall-pass kernels per shard (``fused_sharded/full`` or ``/split``), from the
program's own route counter (capital_tpu/obs/spans.py: ``ROUTES``, which
models/qr.py counts as each build is traced).  It reads 0 when the cell's
build runs the fused pipeline, and when nothing built qr.factor.  A program
without that counter gives None."""

FUSED = "fused_sharded/"


def read(r):
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return None
    routes = getattr(spans, "ROUTES", None)
    if routes is None:
        return None
    return sum(v["builds"] for k, v in routes.snapshot().items()
               if not k.startswith(FUSED))
