"""Share of device busy time under the CQR::chol scope, from own times: the
two Gram factors (potrf + triangular inverse of n × n), computed alike on
every chip from the all-reduced Gram and bound by latency, not by flops."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    s = r.trace.bucket_s("CQR::chol")
    return 100.0 * s / r.trace.busy_s if s > 0 else None
