"""CLI: python -m capital_tpu.autotune
{cholinv,cacqr,trsm,small,blocktri,arrowhead,update} [flags]."""

from __future__ import annotations

import argparse

import jax

from capital_tpu.utils.config import PLATFORM_HELP


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="capital_tpu.autotune")
    p.add_argument("alg", choices=["cholinv", "cacqr", "trsm", "small",
                                   "blocktri", "arrowhead", "update"])
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--m", type=int, default=65536)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--out", default="autotune_out")
    p.add_argument("--bc", type=int, nargs="+", default=None)
    p.add_argument(
        "--modes", nargs="+", default=None,
        choices=["xla", "explicit", "pallas"],
        help="cholinv/trsm: SUMMA modes to sweep (the winning flagship "
        "config is pallas on one TPU for cholinv, xla for trsm — a sweep "
        "that cannot reach it is useless)",
    )
    p.add_argument("--splits", type=int, nargs="+", default=None)
    p.add_argument(
        "--policies", nargs="+", default=None,
        help="cholinv: BaseCasePolicy names (e.g. REPLICATE_COMM_COMP)",
    )
    p.add_argument(
        "--tail-depths", type=int, nargs="+", default=None,
        help="cholinv: tail_fuse_depth values to sweep (fused recursion "
        "tail, CholinvConfig.tail_fuse_depth; 0 = unfused)",
    )
    p.add_argument(
        "--top-k", type=int, default=0,
        help="cholinv: measure only the planner's top-k model candidates",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="checkpoint per-config results under --out and skip configs a "
        "previous (preempted) sweep of the same problem already measured",
    )
    p.add_argument(
        "--grids", nargs="+", default=None,
        help="grid-shape axis (the reference rep-factor loop, "
        "bench/qr/cacqr.cpp:8-25): 'auto' enumerates feasible d x d x c "
        "shapes over the devices (+ flat for cacqr), or explicit "
        "DXxDYxC tokens like 2x2x1 2x2x2 flat",
    )
    p.add_argument(
        "--layouts", type=int, nargs="+", default=None,
        help="device-ordering layouts crossed with each --grids token "
        "(reference topology.h:77-123)",
    )
    p.add_argument(
        "--chunks", type=int, nargs="+", default=None,
        help="num_chunks values crossed with each --grids token (the "
        "reference Ibcast/Iallreduce pipeline; the planner prices q since "
        "round 4)",
    )
    p.add_argument(
        "--op", default="posv", choices=["posv", "lstsq"],
        help="small: which serve op's bucket executables to sweep",
    )
    p.add_argument(
        "--batch", type=int, default=8,
        help="small: bucket batch capacity (ServeConfig.max_batch)",
    )
    p.add_argument(
        "--nrhs", type=int, default=1,
        help="small: RHS columns per problem",
    )
    p.add_argument(
        "--buckets", type=int, nargs="+", default=None,
        help="small: bucket n ladder to sweep, one latency sweep per "
        "bucket (default: 16 32 64 128)",
    )
    p.add_argument(
        "--occupancy", type=float, default=1.0,
        help="small: fixed batch occupancy the latency is measured at "
        "(real problems / capacity; the tail is identity fill, exactly a "
        "serve flush at that occupancy)",
    )
    p.add_argument(
        "--impls", nargs="+", default=None,
        choices=["vmap", "pallas", "pallas_split", "xla", "partitioned"],
        help="small: implementation axis (default all three; 'xla' is the "
        "blocktri baseline impl and 'partitioned' the blocktri Spike "
        "driver, both invalid for small)",
    )
    p.add_argument(
        "--blocks", type=int, nargs="+", default=None,
        help="small/blocktri: column-block unroll axis for the pallas "
        "impls (0 = pick_block default)",
    )
    p.add_argument(
        "--rank", type=int, default=16,
        help="update: rank k of the swept chol_update/chol_downdate panel",
    )
    p.add_argument(
        "--update-op", default="chol_update",
        choices=["chol_update", "chol_downdate"],
        help="update: which maintenance op's bucket executables to sweep",
    )
    p.add_argument(
        "--panels", type=int, nargs="+", default=None,
        help="update: panel-width axis for the xla J-orthogonal impl "
        "(resolve_panel snaps each to a divisor of --n; 0 = auto)",
    )
    p.add_argument(
        "--nblocks", type=int, default=8,
        help="blocktri: chain length (diagonal blocks per problem)",
    )
    p.add_argument(
        "--block", type=int, default=32,
        help="blocktri: block size b (each diagonal block is b x b)",
    )
    p.add_argument(
        "--segs", type=int, nargs="+", default=None,
        help="blocktri: scan-segment-length axis — chain blocks per "
        "pallas_call (resolve_seg snaps each to a divisor of --nblocks; "
        "default 1 4 8)",
    )
    p.add_argument(
        "--partitions", type=int, nargs="+", default=None,
        help="blocktri: partition-count axis for --impls partitioned "
        "(resolve_partitions snaps each to a feasible divisor of "
        "--nblocks; 0 = the √nblocks default; duplicates after snapping "
        "are deduped)",
    )
    p.add_argument(
        "--border", type=int, default=8,
        help="arrowhead: border rank s (rows of the coupling block-row)",
    )
    p.add_argument(
        "--calls", type=int, default=32,
        help="small: per-config latency samples (harness.latency_samples)",
    )
    p.add_argument("--devices", type=int, default=0)
    p.add_argument("--platform", default=None,
                   help=PLATFORM_HELP)
    p.add_argument("--host-devices", type=int, default=0)
    p.add_argument(
        "--ledger", default=None,
        help="append one obs ledger record per swept config to this JSONL "
        "file (query with python -m capital_tpu.obs diff)",
    )
    args = p.parse_args(argv)

    if args.host_devices:
        import os

        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={args.host_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import jax.numpy as jnp

    from capital_tpu.autotune import sweep
    from capital_tpu.parallel.topology import Grid

    dev = jax.devices()
    if args.devices:
        dev = dev[: args.devices]
    dtype = jnp.dtype(args.dtype)
    space = {"bc_dims": tuple(args.bc)} if args.bc else {}
    if args.grids:
        layouts = args.layouts or [0]
        chunks = args.chunks or [0]
        if args.grids == ["auto"]:
            base = sweep.grid_space(dev, include_flat=(args.alg == "cacqr"))
            shapes = [
                None if g.dy == 1 and g.c == 1 and g.dx == len(dev)
                else (g.dx, g.dy, g.c)
                for g in base
            ]
        else:
            shapes = []
            for tok in args.grids:
                if tok == "flat":
                    shapes.append(None)
                    continue
                shapes.append(tuple(int(x) for x in tok.split("x")))
        gs = []
        for shp in shapes:
            if shp is None:
                gs.append(Grid.flat(devices=dev))
                continue
            dx, dy, c = shp
            for lo in layouts:
                for q in chunks:
                    gs.append(
                        Grid.rect(
                            dx, dy, c, devices=dev[: dx * dy * c],
                            layout=lo, num_chunks=q,
                        )
                    )
        space["grids"] = gs
    if args.alg == "cholinv":
        # these knobs exist only in the cholinv space (cacqr sweeps
        # variant x bc x regime)
        if args.modes:
            space["modes"] = tuple(args.modes)
        if args.splits:
            space["splits"] = tuple(args.splits)
        if args.policies:
            from capital_tpu.utils.config import BaseCasePolicy

            space["policies"] = tuple(BaseCasePolicy[p] for p in args.policies)
        if args.tail_depths:
            space["tail_depths"] = tuple(args.tail_depths)
        # with a grid axis the base grid is just a placeholder (every config
        # carries its own); devices counts like 8 have no square c=1 face
        grid = (
            space["grids"][0]
            if "grids" in space
            else Grid.square(c=1, devices=dev)
        )
        res = sweep.tune_cholinv(
            grid, args.n, dtype, args.out, prefilter_top_k=args.top_k,
            checkpoint=args.resume, ledger=args.ledger, **space,
        )
    elif args.alg == "trsm":
        # reject every non-axis rather than silently ignoring it (ADVICE r4:
        # a sweep with --splits would report results that don't reflect it)
        for flag, given in (
            ("--grids", "grids" in space),
            ("--splits", bool(args.splits)),
            ("--policies", bool(args.policies)),
            ("--tail-depths", bool(args.tail_depths)),
            ("--top-k", args.top_k != 0),
            ("--layouts", bool(args.layouts)),
            ("--chunks", bool(args.chunks)),
        ):
            if given:
                p.error(f"{flag} is not a trsm sweep axis (bc x leaf x mode only)")
        if args.modes:
            space["modes"] = tuple(args.modes)
        grid = Grid.square(c=1, devices=dev)
        # the driver's nrhs convention (drivers.py trsm): --m is honored
        # whenever it is not the untouched 65536 default, else nrhs = n
        nrhs = args.m if args.m != 65536 else args.n
        res = sweep.tune_trsm(
            grid, args.n, nrhs, dtype, args.out,
            checkpoint=args.resume, ledger=args.ledger, **space,
        )
    elif args.alg == "small":
        # latency-mode sweep, one per bucket: the objective is per-bucket
        # p99 wall_ms at fixed occupancy, so each bucket n gets its own
        # run_sweep (own checkpoint, own best.json overwritten per bucket
        # is avoided by nesting out dirs per bucket)
        for flag, given in (
            ("--grids", "grids" in space),
            ("--splits", bool(args.splits)),
            ("--policies", bool(args.policies)),
            ("--tail-depths", bool(args.tail_depths)),
            ("--top-k", args.top_k != 0),
            ("--modes", bool(args.modes)),
            ("--bc", bool(args.bc)),
        ):
            if given:
                p.error(
                    f"{flag} is not a small sweep axis (impl x block per "
                    "bucket only)"
                )
        space = {}
        if args.impls:
            if any(i in ("xla", "partitioned") for i in args.impls):
                p.error("--impls xla/partitioned are blocktri impls, not "
                        "small axes (vmap/pallas/pallas_split)")
            space["impls"] = tuple(args.impls)
        if args.blocks:
            space["blocks"] = tuple(args.blocks)
        grid = Grid.square(c=1, devices=dev[:1])
        buckets = args.buckets or [16, 32, 64, 128]
        import os

        res = []
        for n in buckets:
            out_n = os.path.join(args.out, f"n{n}")
            rs = sweep.tune_small(
                grid, args.op, n, batch=args.batch, nrhs=args.nrhs,
                dtype=dtype, out_dir=out_n, occupancy=args.occupancy,
                calls=args.calls, checkpoint=args.resume,
                ledger=args.ledger, **space,
            )
            if not rs:
                # every config's measurement fell below the noise floor
                # (MeasurementUnresolved): skip the bucket, keep sweeping
                print(f"bucket n={n}: no resolved measurements")
                continue
            b = rs[0]
            p99 = (b.extra or {}).get("wall_ms", {}).get("p99")
            print(
                f"bucket n={n}: best {b.config_id}  p99 {p99} ms  "
                f"-> {out_n}/"
            )
            res.extend(rs)
        res.sort(key=lambda r: r.seconds)
    elif args.alg == "blocktri":
        # latency-mode sweep for ONE posv_blocktri bucket: impl x
        # block-unroll x scan-segment-length at fixed occupancy
        for flag, given in (
            ("--grids", "grids" in space),
            ("--splits", bool(args.splits)),
            ("--policies", bool(args.policies)),
            ("--tail-depths", bool(args.tail_depths)),
            ("--top-k", args.top_k != 0),
            ("--modes", bool(args.modes)),
            ("--bc", bool(args.bc)),
            ("--buckets", bool(args.buckets)),
        ):
            if given:
                p.error(
                    f"{flag} is not a blocktri sweep axis (impl x block x "
                    "seg only)"
                )
        space = {}
        if args.impls:
            if any(i in ("vmap", "pallas_split") for i in args.impls):
                p.error("blocktri impls are 'xla', 'pallas' and "
                        "'partitioned' only")
            space["impls"] = tuple(args.impls)
        if args.blocks:
            space["blocks"] = tuple(args.blocks)
        if args.segs:
            space["segs"] = tuple(args.segs)
        if args.partitions:
            space["partitions"] = tuple(args.partitions)
        grid = Grid.square(c=1, devices=dev[:1])
        res = sweep.tune_blocktri(
            grid, args.nblocks, args.block, batch=args.batch,
            nrhs=args.nrhs, dtype=dtype, out_dir=args.out,
            occupancy=args.occupancy, calls=args.calls,
            checkpoint=args.resume, ledger=args.ledger, **space,
        )
    elif args.alg == "arrowhead":
        # latency-mode sweep for ONE posv_arrowhead bucket: impl x
        # border blocking x scan-segment-length at fixed occupancy
        for flag, given in (
            ("--grids", "grids" in space),
            ("--splits", bool(args.splits)),
            ("--policies", bool(args.policies)),
            ("--tail-depths", bool(args.tail_depths)),
            ("--top-k", args.top_k != 0),
            ("--modes", bool(args.modes)),
            ("--bc", bool(args.bc)),
            ("--buckets", bool(args.buckets)),
        ):
            if given:
                p.error(
                    f"{flag} is not an arrowhead sweep axis (impl x block "
                    "x seg only)"
                )
        space = {}
        if args.impls:
            if any(i in ("vmap", "pallas_split") for i in args.impls):
                p.error("arrowhead impls are 'xla', 'pallas' and "
                        "'partitioned' only")
            space["impls"] = tuple(args.impls)
        if args.blocks:
            space["blocks"] = tuple(args.blocks)
        if args.segs:
            space["segs"] = tuple(args.segs)
        if args.partitions:
            space["partitions"] = tuple(args.partitions)
        grid = Grid.square(c=1, devices=dev[:1])
        res = sweep.tune_arrowhead(
            grid, args.nblocks, args.block, border=args.border,
            batch=args.batch, nrhs=args.nrhs, dtype=dtype, out_dir=args.out,
            occupancy=args.occupancy, calls=args.calls,
            checkpoint=args.resume, ledger=args.ledger, **space,
        )
    elif args.alg == "update":
        # latency-mode sweep for ONE chol_update/chol_downdate bucket:
        # impl x block-unroll (pallas) / panel (xla) at fixed occupancy
        for flag, given in (
            ("--grids", "grids" in space),
            ("--splits", bool(args.splits)),
            ("--policies", bool(args.policies)),
            ("--tail-depths", bool(args.tail_depths)),
            ("--top-k", args.top_k != 0),
            ("--modes", bool(args.modes)),
            ("--bc", bool(args.bc)),
            ("--buckets", bool(args.buckets)),
            ("--segs", bool(args.segs)),
            ("--partitions", bool(args.partitions)),
        ):
            if given:
                p.error(
                    f"{flag} is not an update sweep axis (impl x "
                    "block/panel only)"
                )
        space = {}
        if args.impls:
            if any(i in ("vmap", "pallas_split") for i in args.impls):
                p.error("update impls are 'xla' and 'pallas' only")
            space["impls"] = tuple(args.impls)
        if args.blocks:
            space["blocks"] = tuple(args.blocks)
        if args.panels:
            space["panels"] = tuple(args.panels)
        grid = Grid.square(c=1, devices=dev[:1])
        res = sweep.tune_update(
            grid, args.n, args.rank, batch=args.batch, op=args.update_op,
            dtype=dtype, out_dir=args.out, occupancy=args.occupancy,
            calls=args.calls, checkpoint=args.resume, ledger=args.ledger,
            **space,
        )
    else:
        grid = Grid.flat(devices=dev)
        res = sweep.tune_cacqr(grid, args.m, args.n if args.n < args.m else 512,
                               dtype, args.out, checkpoint=args.resume,
                               ledger=args.ledger, **space)
    if not res:
        print(f"no resolved measurements -> {args.out}/")
        return
    best = res[0]
    print(f"best: {best.config_id}  {best.seconds * 1e3:.3f} ms  -> {args.out}/")


if __name__ == "__main__":
    from capital_tpu.utils import compile_cache

    compile_cache.enable()
    main()
