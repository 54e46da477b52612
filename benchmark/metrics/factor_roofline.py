"""The algorithm's flops for the factors completed in the traced window, over
(chips × bf16 peak × device busy time).  Flops come from the shapes
(configs/<config>.py `flops`), never from the padded or executed work, so
this reads the same work whatever implements it.  Both programs sit far above
the v5e ridge of 197e12 / 819e9 ≈ 240 flop/byte (cholinv at n=49152 does
about n/3 flops per byte of its three n² bf16 buffers; CholeskyQR2 at
n=1024 does about n flops per byte of A), so compute bounds them and the
roofline is the bf16 peak."""


def read(r):
    flops = r.counters.get("window_flops")
    busy = r.trace.busy_s * r.chips if r.trace else 0.0
    if not flops or busy <= 0 or r.peak is None:
        return None
    return 100.0 * flops / (r.peak.bf16_flops * busy)
