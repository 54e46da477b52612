"""Pallas kernels the program built, from its own kernel counter
(capital_tpu/obs/spans.py: ``KERNELS``, whose ``built`` counts the kernel
bodies its kernel cache traced).  A driver builds everything in set-up and
the reference imports nothing of the program, so the count read after the
run is the count of set-up.  A program without that counter gives None."""


def read(r):
    try:
        from capital_tpu.obs import spans
    except ImportError:
        return None
    kernels = getattr(spans, "KERNELS", None)
    return None if kernels is None else kernels.snapshot()["built"]
