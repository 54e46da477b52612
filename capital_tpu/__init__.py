"""capital_tpu — a TPU-native communication-avoiding dense linear algebra framework.

A ground-up JAX / XLA / Pallas re-design of the capabilities of the reference
CAPITAL library (communication-avoiding parallel schedules for dense matrix
factorizations): 3D SUMMA matrix multiplication, communication-optimal recursive
Cholesky factorization with simultaneous triangular inverse, communication-
avoiding CholeskyQR2 for tall-skinny matrices, distributed triangular inversion,
Newton-Schulz iterative inversion, and the surrounding validation / benchmark /
autotune harness.

Where the reference expresses parallelism through MPI communicator splits over a
d x d x c process grid (reference: src/util/topology.h) and delegates local
compute to MKL BLAS/LAPACK (reference: src/blas/interface.hpp,
src/lapack/interface.hpp), this framework expresses the same schedules on a TPU
device mesh: axis-scoped collectives (psum, all_gather, ppermute) inside
shard_map over ICI/DCN, dense masked tiles instead of packed triangular
storage, lax.linalg plus Pallas kernels for panel factorizations, and
trace-time block scheduling in place of runtime recursion.

Package layout:
  parallel/  - device-mesh topology, collectives, SUMMA (reference L2 + L4 matmult)
  ops/       - local compute engines: BLAS/LAPACK equivalents, masks, Pallas kernels
               (reference L3' src/blas + src/lapack)
  models/    - the algorithm families: cholesky (cholinv), qr (cacqr),
               inverse (rectri/newton), trsm (reference L4 src/alg)
  utils/     - deterministic fillers, residual validation (gates and test
               operands), tracing, config (reference src/util + test/ +
               critter shims)
  robust/    - breakdown detection, recovery, refinement
  serve/     - the batching solve engine, its stats and router
  obs/, lint/ - program audits, ledgers, spans; the program sanitizer
  bench/     - per-algorithm benchmark CLI (reference bench/); a leaf:
               only autotune/ imports it
  autotune/  - config sweep harness and its alpha-beta schedule planner
               (reference autotune/)

The measurement of record is the repo's benchmark/ harness, not bench/.
"""

__version__ = "0.1.0"


def __getattr__(name: str):
    # Grid resolves lazily (PEP 562): importing it pulls in jax, and the
    # host-only serve processes (router pumps, spawned loadgen clients)
    # import this package without ever needing a device runtime.
    if name == "Grid":
        from capital_tpu.parallel.topology import Grid

        globals()["Grid"] = Grid
        return Grid
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
