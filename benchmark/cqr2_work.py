"""The work of CholeskyQR2's three tall passes, for their roofline shares.

Operations and bytes come from the cacqr-tall configuration
(configs/cacqr-tall.json: m, n and the dtype of A, b bytes an element), never
from the padded or executed work: the fused kernels execute (g+1)/2g of each
dense product and are counted here at the algorithm's count.  Per factor:

=============  ======================  =========  ============================
phase          kernel                  flops      bytes
=============  ======================  =========  ============================
``CQR::gram``  ``CQR.gram.gram``       m·n²       read A: m·n·b
``CQR::fused`` ``CQR.fused.scale_gram`` 2m·n²     read A, write Q1: 2m·n·b
``CQR::formR`` ``CQR.formR.scale``     m·n²       read Q1, write Q: 2m·n·b
=============  ======================  =========  ============================

(the Gram AᵀA is symmetric and the scale A·R⁻¹ triangular: m·n² each; the
three make the 4m·n² of configs/cacqr-tall.py ``flops``).  A pass's roofline
on one chip is min(bf16 peak, HBM bandwidth × flops/bytes): n/2 flop/byte for
the first two, n/4 for the last, against the v5e ridge of about 240.

A phase's own time in the trace is its one Pallas kernel plus n²-element
work beside it: the Gram's cast to A's dtype (``CQR::gram``), the triu of
R1⁻¹ and the cast of the second Gram (``CQR::fused``), the triu of R2⁻¹
(``CQR::formR``).  Each Gram's psum across chips goes to the ``collective``
bucket (trace_reduce.bucket), not to its phase.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "configs", "cacqr-tall.json")

#: phase -> (flops in m·n², bytes in m·n·b)
PASSES = {"CQR::gram": (1, 1), "CQR::fused": (2, 2), "CQR::formR": (1, 2)}


def work(phase: str) -> tuple[float, float]:
    """(flops, bytes) of one factor's `phase` pass at cacqr-tall's sizes."""
    import jax.numpy as jnp

    with open(CONFIG) as f:
        cfg = json.load(f)
    m, n = int(cfg["m"]), int(cfg["n"])
    kf, kb = PASSES[phase]
    return kf * m * n * n, kb * m * n * jnp.dtype(cfg["dtype"]).itemsize


def roofline_share(reading, phase: str):
    """The window's algorithm flops of `phase` over (chips × the phase's
    own seconds × its roofline), in %; None when the trace has no own time
    in the phase or the reading lacks the factor count or the peak."""
    own = reading.trace.bucket_s(phase) if reading.trace else 0.0
    factors = reading.counters.get("factors")
    if own <= 0 or not factors or reading.peak is None:
        return None
    flops, nbytes = work(phase)
    pk = reading.peak
    roof = min(pk.bf16_flops, pk.hbm_bytes_per_s * flops / nbytes)
    return 100.0 * factors * flops / (reading.chips * own * roof)
