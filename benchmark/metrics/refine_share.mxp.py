"""Share of device busy time under the refinement's phases, from own times:
``IR::residual`` (the FP64-grade residuals and the ‖A‖∞ pass) and
``IR::correct`` (the corrections against the bf16 factor), against the
factor's ``CI::*`` phases in the same dispatch."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    s = r.trace.bucket_s("IR::residual", "IR::correct")
    return 100.0 * s / r.trace.busy_s if s > 0 else None
