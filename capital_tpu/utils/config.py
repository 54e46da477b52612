"""Runtime configuration enums and dataclasses.

The reference configures algorithms through four mechanisms (SURVEY §5.6):
positional argv, env vars, compile-time -D flags, and — the real one —
template policy selection (e.g. cholinv<Serialize,SaveIntermediates,
NoReplication>, bench/cholesky/cholinv.cpp:31-33).  JAX retracing replaces
template instantiation, so every policy becomes a runtime enum here; configs
hash into jit static args, giving one compiled executable per configuration,
exactly like one template instantiation per policy combination.
"""

from __future__ import annotations

import enum

#: help text of every CLI's ``--platform`` flag (the flag sets
#: ``jax_platforms`` through the config API before the first backend use)
PLATFORM_HELP = (
    "JAX platform to run on (e.g. cpu); default: JAX_PLATFORMS, else "
    "JAX's own choice"
)


class BaseCasePolicy(enum.Enum):
    """Base-case execution strategies (reference cholinv/policy.h:160-514).

    The reference trades replicated computation against gather/scatter
    communication on CPU clusters.  On a TPU mesh, replicating a small
    panel (one all_gather over ICI) and computing it redundantly on every
    chip is usually cheapest (redundant small-matrix compute is free
    relative to extra collectives — SURVEY §7.1), but all four strategies
    are genuinely implemented so the trade is measurable, not asserted
    (models/cholesky.py:_base_case_into / _scoped_base_factor):

      REPLICATE_COMM_COMP   gather to every device, every device factors the
                            panel (TPU default; reference policy.h:160-224
                            'ReplicateCommComp')
      REPLICATE_COMP        only the z=0 depth layer factors; the result is
                            broadcast down 'z' as a psum of the layer-masked
                            value (reference policy.h:226-305)
      NO_REPLICATION        only the root device (0,0,0) factors; the result
                            is broadcast over the whole mesh (reference
                            gather-to-root + scatter, policy.h:307-414)
      NO_REPLICATION_OVERLAP same schedule as NO_REPLICATION; the reference
                            overlaps the scatter with trtri by hand
                            (policy.h:416-514) — on TPU, XLA's
                            latency-hiding scheduler owns that overlap
    """

    REPLICATE_COMM_COMP = 0
    REPLICATE_COMP = 1
    NO_REPLICATION = 2
    NO_REPLICATION_OVERLAP = 3

    @property
    def compute_scope(self) -> str:
        """Which devices run the panel factorization: 'all' | 'layer' |
        'root' (see class docstring)."""
        if self is BaseCasePolicy.REPLICATE_COMM_COMP:
            return "all"
        if self is BaseCasePolicy.REPLICATE_COMP:
            return "layer"
        return "root"
