"""Plain reference for serve-solve: float64 NumPy on the host.

posv: X = A⁻¹B by LU (numpy.linalg.solve).  lstsq: X = argmin ‖AX − B‖ by
Householder QR (numpy.linalg.qr) and a solve with R.  It imports nothing of
the program and sees only the request's own A and B.
"""

from __future__ import annotations

import numpy as np


def solve(op: str, A, B) -> np.ndarray:
    A = np.asarray(A, np.float64)
    B = np.asarray(B, np.float64)
    if op == "posv":
        return np.linalg.solve(A, B)
    if op == "lstsq":
        Q, R = np.linalg.qr(A)
        return np.linalg.solve(R, Q.T @ B)
    raise ValueError(f"no reference for op {op!r}")
