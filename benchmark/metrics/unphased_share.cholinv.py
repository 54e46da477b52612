"""Share of device busy time in ops that carry no PHASE_REGISTRY tag, from own
times: trace_reduce gives an op the registered phase in its HLO op_name or
its own name, and what has neither falls to a kind (``fusion``, ``copy``,
``custom-call``, ``other``), summed here.  The benchmark's own operand
generator is such an op (a fusion in the factor's dispatch, outside the
program), so the share never reaches 0: on a TPU v5e at n=49152 XLA's
rematerialization computes it three times per factor, 6.9% of busy time.
Collectives keep a bucket of their own whatever their scope and are not
counted (one chip has none)."""

UNPHASED = ("fusion", "copy", "custom-call", "other")


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * r.trace.bucket_s(*UNPHASED) / r.trace.busy_s
