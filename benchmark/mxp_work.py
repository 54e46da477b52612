"""The bytes of the dense refined solve's FP64-grade residual, for its
roofline share.

Counted from the hplmxp-spd configuration (configs/hplmxp-spd.json: n and
A's dtype, b bytes an element), never from what XLA executes.  Under
``IR::residual`` a solve reads A once to scale the check (‖A‖∞) and once
per residual r = b − A·x, which it evaluates once more than it corrects:

============================  =========================================
per solve                     bytes
============================  =========================================
‖A‖∞                          n²·b
each residual                 n²·b + 16n (b, x_hi, x_lo read; r written,
                              4 bytes each)
============================  =========================================

The residual does 2n² flops on n²·b bytes, about 1 flop a byte against the
v5e ridge of about 240: HBM bandwidth bounds it, so its roofline is the
bytes over the bandwidth.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "configs", "hplmxp-spd.json")


def residual_bytes(solves: int, residuals: int) -> float:
    """Bytes read and written under IR::residual by `solves` solves that
    evaluated `residuals` residuals between them."""
    import jax.numpy as jnp

    with open(CONFIG) as f:
        cfg = json.load(f)
    n = int(cfg["n"])
    a = n * n * jnp.dtype(cfg["dtype"]).itemsize
    return solves * a + residuals * (a + 16.0 * n)


def residual_roofline(reading):
    """The window's residual bytes over (own seconds under IR::residual ×
    HBM bandwidth), in %; None when the trace has no own time there or the
    reading lacks the counts or the peak."""
    own = reading.trace.bucket_s("IR::residual") if reading.trace else 0.0
    solves = reading.counters.get("solves")
    residuals = reading.counters.get("residuals")
    if own <= 0 or not solves or not residuals or reading.peak is None:
        return None
    return 100.0 * residual_bytes(solves, residuals) / (
        own * reading.peak.hbm_bytes_per_s)
