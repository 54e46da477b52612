"""The FP64-grade residual's share of its roofline: the window's bytes under
``IR::residual`` (A's n² elements a residual and once a solve for ‖A‖∞,
plus the vectors; mxp_work.py) over (own seconds under ``IR::residual`` ×
HBM bandwidth).  Memory bounds it: about 1 flop a byte."""

import mxp_work


def read(r):
    return mxp_work.residual_roofline(r)
