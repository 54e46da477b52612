"""CQR.formR.scale's share of its roofline: the window's algorithm flops of
the final scale Q = Q1·R2⁻¹, m·n² a factor, over (chips × own seconds under
``CQR::formR`` × min(bf16 peak, HBM bandwidth × n/4 flop/byte)).  The
phase's own time is the kernel and the triu of R2⁻¹ (cqr2_work.py)."""

import cqr2_work


def read(r):
    return cqr2_work.roofline_share(r, "CQR::formR")
