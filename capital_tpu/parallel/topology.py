"""Device-mesh topology: the TPU-native equivalent of CAPITAL's process grids.

The reference (src/util/topology.h) builds 3D process grids by splitting MPI
communicators: ``topo::square`` is a d x d x c grid (face d x d, replication
depth c) with named sub-communicators {world, row, column, slice, depth};
``topo::rect`` is a tunable c x d grid for tall-skinny QR with extra
{cube, column_contig, column_alt} sub-communicators (topology.h:16-143).

On TPU the whole layer collapses to a `jax.sharding.Mesh` with named axes
``('x', 'y', 'z')`` plus sharding helpers:

  - sub-communicator  ->  mesh axis name used by an axis-scoped collective
        row    comm (vary x, fixed y,z)  ->  collectives over axis 'x'
        column comm (vary y, fixed x,z)  ->  collectives over axis 'y'
        depth  comm (vary z)             ->  collectives over axis 'z'
        slice  comm (vary x,y)           ->  collectives over ('x', 'y')
        world                            ->  collectives over ('x', 'y', 'z')
  - grid coordinates (x,y,z)  ->  `jax.lax.axis_index` inside shard_map
  - communicator free/destructor -> nothing (meshes are cheap values)

Matrix distribution convention (used throughout the framework): a global
(M, N) array is **block**-distributed with rows split over mesh axis 'x' and
columns over mesh axis 'y', replicated over 'z' — i.e.
``NamedSharding(mesh, P('x', 'y'))``.  Note this deliberately differs from the
reference, which distributes *element-cyclically* over the PgridX x PgridY
face (structure.hpp strides global positions by the grid dims per local
element; matrix.hpp:6-18): cyclic layout exists there to load-balance
triangular work, which this framework instead handles with block-level
masking/predication, while contiguous blocks are what XLA/MXU tiling wants.
Matrix *content* stays comparable across the two layouts because fillers are
seeded from global coordinates (see utils/rand48.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("x", "y", "z")


def _infer_square_face(num_devices: int, c: int) -> int:
    """d = sqrt(P / c), the face dimension of a d x d x c grid.

    Mirrors topo::square's ``d = ceil(sqrt(size/c))`` (topology.h:76-78), but
    requires exact divisibility: TPU meshes cannot leave devices idle.
    """
    if num_devices % c != 0:
        raise ValueError(f"num_devices={num_devices} not divisible by c={c}")
    face = num_devices // c
    d = int(round(math.sqrt(face)))
    if d * d != face:
        raise ValueError(
            f"num_devices/c = {face} is not a perfect square; "
            f"cannot build a d x d x {c} grid from {num_devices} devices"
        )
    return d


def layout2_eligible(dx: int, dy: int, c: int) -> bool:
    """Whether the 2x2x2-subcube device ordering (layout=2) applies to this
    grid shape — the single source of truth for the fallback condition in
    _order_devices and for callers choosing a layout programmatically."""
    return dx % 2 == 0 and dy % 2 == 0 and c % 2 == 0


def _order_devices(
    devices: Sequence[jax.Device], dx: int, dy: int, c: int, layout: int
) -> np.ndarray:
    """Assign devices to (x, y, z) grid coordinates — the TPU analog of the
    reference's rank->coordinate ``layout`` variants (topology.h:77-123).

    On an MPI cluster the layout decides which ranks share a node; on a TPU
    slice it decides which mesh axes map to adjacent ICI links (device order
    is physical on real slices), so it is the same locality knob:

      0  depth-fastest (reference layout 0: z = rank % c) — consecutive
         devices stack along the replication axis, so the depth allreduce
         rides the shortest links.  The natural reshape.  NOTE the face
         orientation is transposed relative to the reference's coordinate
         assignment (topology.h:81-83 is z-fastest, then x, then y; this
         reshape is z, then y, then x): row- and column-broadcast locality
         are swapped, so layout-sweep rows here are not directly comparable
         against reference layout-0 data — compare 0 vs 1 vs 2 within this
         framework only.
      1  face-fastest (reference layout 1 family) — consecutive devices tile
         the d x d face first; row/column bcasts get the short links, depth
         gets the long ones.
      2  subcube blocking (reference layout 2, the 64-rank subcube variant,
         topology.h:104-123) — consecutive groups of 8 devices form 2x2x2
         subcubes, balancing all three axes; falls back to layout 0 when any
         dimension is odd.
    """
    dev = np.asarray(devices, dtype=object)
    if layout == 0:
        return dev.reshape(dx, dy, c)
    if layout == 1:
        return np.moveaxis(dev.reshape(c, dx, dy), 0, 2)
    if layout == 2:
        if not layout2_eligible(dx, dy, c):
            import warnings

            warnings.warn(
                f"layout=2 needs even grid dims, got {(dx, dy, c)}: "
                "falling back to layout 0 (a layout-0-vs-2 comparison on "
                "this grid would silently measure the same ordering)",
                stacklevel=3,
            )
            return dev.reshape(dx, dy, c)
        # consecutive groups of 8 devices form 2x2x2 subcubes, block-major
        # over the (dx/2, dy/2, c/2) grid of subcubes
        return (
            dev.reshape(dx // 2, dy // 2, c // 2, 2, 2, 2)
            .transpose(0, 3, 1, 4, 2, 5)
            .reshape(dx, dy, c)
        )
    raise ValueError(f"layout must be 0, 1, or 2, got {layout}")


@dataclasses.dataclass(frozen=True)
class Grid:
    """A d x d x c (or dx x dy x c) device grid backed by a jax Mesh.

    TPU-native stand-in for ``topo::square`` / ``topo::rect``
    (reference src/util/topology.h:16-143).

    Attributes:
      mesh: Mesh with axes ('x', 'y', 'z') of shape (dx, dy, c).
      c:    replication depth (the 'z' axis extent) — trades memory for
            communication exactly like the reference's rep_factor.
      num_chunks: SUMMA communication-pipelining granularity, carried on the
            topology exactly like the reference's ctor argument
            (topo::square(world, c, layout, num_chunks), topology.h:67):
            the explicit schedule splits each K-panel broadcast into this
            many slices so the compiler can overlap each slice's collective
            with the previous slice's local matmul (the Ibcast/Iallreduce
            pipeline of summa.hpp:196-215).  0/1 = unchunked.
      collective_concurrency: 'free' (default) lets XLA's latency-hiding
            scheduler put any number of the explicit schedule's collectives
            in flight; 'solo' chains every collective in a SUMMA invocation
            behind the previous one (optimization_barrier data dependency),
            so at most one is on the wire at a time — the runtime
            re-expression of the reference's COLLECTIVE_CONCURRENCY_SOLO
            congestion experiment (compile flag, summa.hpp:179-192,
            230-235).  The reference's LAYER variant (per-depth-layer
            serialization) is subsumed: each depth layer's collectives
            already form a chain per device in 'solo', and XLA schedules
            per-program, not per-layer.  Same bytes and collective count —
            only the overlap changes, which the alpha-beta cost model does
            not price (it models launches, the scheduler owns overlap).
    """

    mesh: Mesh
    num_chunks: int = 0
    collective_concurrency: str = "free"
    layout: int = 0  # record of the device-ordering knob used at
    # construction (the ordering itself lives in mesh.devices); carried so
    # sweep rows over the layout axis stay attributable (reference
    # topology.h ctor arg)

    # ---- constructors ------------------------------------------------------

    @staticmethod
    def square(
        c: int = 1,
        devices: Optional[Sequence[jax.Device]] = None,
        layout: int = 0,
        num_chunks: int = 0,
        collective_concurrency: str = "free",
    ) -> "Grid":
        """Build a d x d x c grid from all (or the given) devices.

        Reference: topo::square ctor, topology.h:67-131.  ``layout`` is the
        reference's rank->coordinate assignment knob (topology.h:77-123) —
        on TPU it is the device-order-into-mesh permutation, the lever that
        decides which mesh axes ride adjacent ICI links (see _order_devices).
        """
        devices = list(devices if devices is not None else jax.devices())
        d = _infer_square_face(len(devices), c)
        return Grid(
            mesh=Mesh(_order_devices(devices, d, d, c, layout), AXES),
            num_chunks=num_chunks,
            collective_concurrency=collective_concurrency,
            layout=layout,
        )

    @staticmethod
    def largest_square(
        devices: Sequence[jax.Device],
        c: int = 1,
        layout: int = 0,
        num_chunks: int = 0,
    ) -> "Grid":
        """The largest d x d x c grid the given devices support, preferring
        replication depth ``c`` (reference rep_div knob,
        bench/cholesky/cholinv.cpp:16) and trying 1, 2, 4, 8 after it;
        devices past d*d*c stay unused."""
        devices = list(devices)
        n = len(devices)
        if n == 1:
            return Grid.square(c=1, devices=devices, num_chunks=num_chunks)
        best = (1, 1)  # (d, c)
        for cc in (c, 1, 2, 4, 8):
            d = 1
            while (d + 1) * (d + 1) * cc <= n:
                d += 1
            if d * d * cc <= n and d * d * cc > best[0] ** 2 * best[1]:
                best = (d, cc)
        d, c = best
        return Grid.square(
            c=c, devices=devices[: d * d * c], layout=layout,
            num_chunks=num_chunks,
        )

    @staticmethod
    def rect(
        dx: int,
        dy: int,
        c: int = 1,
        devices: Optional[Sequence[jax.Device]] = None,
        layout: int = 0,
        num_chunks: int = 0,
        collective_concurrency: str = "free",
    ) -> "Grid":
        """Build a dx x dy x c grid (tunable shape, reference topo::rect).

        Reference: topology.h:16-65.  The reference's rect grid carries extra
        sub-communicators (cube, column_contig, column_alt) used by
        cacqr's tunable sweep; here those become axis subsets at collective
        call sites (see models/qr.py).
        """
        devices = list(devices if devices is not None else jax.devices())
        if dx * dy * c != len(devices):
            raise ValueError(f"{dx}*{dy}*{c} != {len(devices)} devices")
        return Grid(
            mesh=Mesh(_order_devices(devices, dx, dy, c, layout), AXES),
            num_chunks=num_chunks,
            collective_concurrency=collective_concurrency,
            layout=layout,
        )

    @staticmethod
    def flat(devices: Optional[Sequence[jax.Device]] = None) -> "Grid":
        """A P x 1 x 1 grid: every device along 'x'.

        Used for the 1D tall-skinny regime (cacqr's c==1 path,
        reference cacqr.hpp:7-29) where the long axis is sharded over all
        devices and everything else is replicated.
        """
        devices = list(devices if devices is not None else jax.devices())
        dev = np.asarray(devices).reshape(len(devices), 1, 1)
        return Grid(mesh=Mesh(dev, AXES))

    # ---- geometry ----------------------------------------------------------

    @property
    def dx(self) -> int:
        return self.mesh.shape["x"]

    @property
    def dy(self) -> int:
        return self.mesh.shape["y"]

    @property
    def c(self) -> int:
        return self.mesh.shape["z"]

    @property
    def num_devices(self) -> int:
        return self.dx * self.dy * self.c

    @property
    def is_square(self) -> bool:
        return self.dx == self.dy

    @property
    def platform(self) -> str:
        """Platform of the mesh's devices ('tpu'/'cpu'/...).  Kernel dispatch
        must key off this, never jax.default_backend(): a CPU mesh can live in
        a TPU-backed process (the driver's multichip dryrun)."""
        return self.mesh.devices.ravel()[0].platform

    # ---- sharding helpers --------------------------------------------------

    def face_sharding(self) -> NamedSharding:
        """Block distribution over the grid face, replicated over depth.

        The standard layout for every distributed matrix in the framework:
        rows over 'x', columns over 'y' (reference matrix.hpp:6-18).
        """
        return NamedSharding(self.mesh, P("x", "y"))

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def rows_sharding(self) -> NamedSharding:
        """Long-axis distribution: rows over all three axes, cols replicated.

        The tall-skinny layout (reference: Q registered on the full c x d
        rect grid, cacqr.hpp:224)."""
        return NamedSharding(self.mesh, P(("x", "y", "z"), None))

    def spec(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def pin(self, x):
        """Constrain a 2D array to the face layout when its shape divides the
        face, else leave placement to XLA (uneven explicit shardings are
        rejected by jit; odd-sized recursion windows hit this).  The fallback
        is announced — a distributed run with a misaligned n would otherwise
        silently lose the intended layout (pad to a divisible size upstream
        to avoid it)."""
        if x.ndim == 2 and x.shape[0] % self.dx == 0 and x.shape[1] % self.dy == 0:
            return jax.lax.with_sharding_constraint(x, self.face_sharding())
        if self.num_devices > 1:
            from capital_tpu.utils import tracing

            tracing.note("pin::fallback")
            import warnings

            warnings.warn(
                f"Grid.pin: shape {tuple(x.shape)} does not divide the "
                f"{self.dx}x{self.dy} face; placement left to XLA",
                stacklevel=2,
            )
        return x

    # ---- shape utilities ---------------------------------------------------

    def face_tile(self, m: int, n: int) -> tuple[int, int]:
        """Padded global shape so (rows, cols) divide evenly over (dx, dy).

        The reference pads implicitly with zero rows/cols per-rank
        (structure.hpp:42-43, matrix.hpp:7-11); here padding happens once,
        globally, at the boundary (SURVEY §7.1 'pad-to-tile')."""
        pm = -(-m // self.dx) * self.dx
        pn = -(-n // self.dy) * self.dy
        return pm, pn

    def __repr__(self) -> str:  # pragma: no cover
        chunks = f", chunks={self.num_chunks}" if self.num_chunks > 1 else ""
        return (
            f"Grid({self.dx}x{self.dy}x{self.c}, "
            f"{self.mesh.devices.ravel()[0].platform}{chunks})"
        )


def cpu_grid_square(c: int = 1, n: Optional[int] = None) -> Grid:
    """Square grid over host-platform (CPU) devices — the multi-chip test rig.

    The reference tests distributed behavior by oversubscribed ``mpirun -n 8``
    (SURVEY §4); the equivalent here is N virtual CPU devices via
    ``--xla_force_host_platform_device_count`` (see tests/conftest.py).
    """
    devices = jax.devices("cpu")
    if n is not None:
        if n > len(devices):
            raise ValueError(
                f"requested {n} CPU devices but only {len(devices)} exist "
                "(raise --xla_force_host_platform_device_count)"
            )
        devices = devices[:n]
    return Grid.square(c=c, devices=devices)
