"""Share of device busy time under the CI::factor_diag and CI::tail_fused
scopes (the recursion's base case, fused or not), from own times."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    s = r.trace.bucket_s("CI::factor_diag", "CI::tail_fused")
    return 100.0 * s / r.trace.busy_s if s > 0 else None
