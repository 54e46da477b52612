"""CQR.gram.gram's share of its roofline: the window's algorithm flops of the
first Gram, m·n² a factor, over (chips × own seconds under ``CQR::gram`` ×
min(bf16 peak, HBM bandwidth × n/2 flop/byte)).  The phase's own time is the
kernel and the Gram's n² cast; its psum is in ``collective``
(cqr2_work.py)."""

import cqr2_work


def read(r):
    return cqr2_work.roofline_share(r, "CQR::gram")
