"""CQR.fused.scale_gram's share of its roofline: the window's algorithm flops
of the first scale and the second Gram, 2m·n² a factor, over (chips × own
seconds under ``CQR::fused`` × min(bf16 peak, HBM bandwidth × n/2
flop/byte)).  The phase's own time is the kernel, the triu of R1⁻¹ and the
second Gram's n² cast; its psum is in ``collective`` (cqr2_work.py)."""

import cqr2_work


def read(r):
    return cqr2_work.roofline_share(r, "CQR::fused")
