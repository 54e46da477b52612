"""chip_smoke.py — drive capital-tpu's main paths once on a TPU and check them.

    python3 chip_smoke.py              # one chip: cholinv, cacqr, serve
    python3 chip_smoke.py --chips 4    # four chips: mesh cholinv, sharded
                                       # CQR2, a 4-replica Router

Sizes are BASELINE.md's: cholinv N=16384 bf16 (bc from
cholesky.pick_base_case), single-rank CholeskyQR2 65536x512 f32, and a
SolveEngine answering f32 posv/lstsq over three small-N buckets (n <= 128,
the Pallas batched-grid route) and one mid-n bucket.  Operands are made on the device from
``--seed``.  Every result is checked: the factorizations by the repo's
residual gates (utils/residual, residual.tolerance per dtype), served
answers against a NumPy f64 solve, and the cholinv/serve programs must
contain ``tpu_custom_call`` (Mosaic ran, not the interpreter).

One JSON line per phase, then the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check, a device that is not a TPU, or a package that is not
next to this file exits non-zero without that line.  One process holds
the chip(s) throughout; nothing is spawned.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# BASELINE.md configurations
CHOLINV_N = 16384
CQR_M, CQR_N = 65536, 512
SMALL_NS = (12, 24, 48)  # -> buckets 16 / 32 / 64 (Pallas route)
MID_N = 384  # -> bucket 512 (vmap route)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _timed_compile(fn, *args):
    import jax

    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    return exe, time.perf_counter() - t0


def _timed_run(exe, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, time.perf_counter() - t0


def _chol_gates(A, R, Rinv) -> tuple[float, float]:
    """‖A−RᵀR‖/‖A‖ and ‖I−R·Rinv‖/‖I‖ at f32 (a bf16 sum of n² squares
    would be the gate's own noise)."""
    import jax
    import jax.numpy as jnp

    from capital_tpu.utils import residual

    f32 = jnp.float32

    @jax.jit
    def gates(a, r, ri):
        r = r.astype(f32)
        return (residual.cholesky_residual(a.astype(f32), r),
                residual.cholesky_inverse_residual(r, ri.astype(f32)))

    fr, ir = gates(A, R, Rinv)
    return float(fr), float(ir)


def _qr_gates(A, Q, R) -> tuple[float, float]:
    import jax

    from capital_tpu.utils import residual

    o, r = jax.jit(lambda a, q, rr: (residual.qr_orthogonality(q),
                                     residual.qr_residual(a, q, rr)))(A, Q, R)
    return float(o), float(r)


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def phase_cholinv(dev, seed: int, n: int = CHOLINV_N) -> dict:
    import jax.numpy as jnp

    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.utils.residual import spd_operand, tolerance

    dtype = jnp.bfloat16
    grid = Grid.square(c=1, devices=[dev])
    cfg = cholesky.CholinvConfig(
        base_case_dim=cholesky.pick_base_case(n), mode="pallas")
    A = spd_operand(n, dtype, seed)
    exe, compile_s = _timed_compile(lambda a: cholesky.factor(grid, a, cfg), A)
    (R, Rinv), run_s = _timed_run(exe, A)
    fr, ir = _chol_gates(A, R, Rinv)
    tol = tolerance(dtype)
    line = {"phase": "cholinv", "n": n, "bc": cfg.base_case_dim,
            "dtype": "bfloat16", "mode": "pallas",
            "compile_s": compile_s, "run_s": run_s,
            "factor_resid": fr, "inverse_resid": ir, "tol": tol,
            "tpu_custom_calls": _custom_calls(exe)}
    _check(line["tpu_custom_calls"] > 0, "cholinv: no tpu_custom_call")
    _check(fr < tol and ir < tol, f"cholinv residuals {fr:.3e}/{ir:.3e}")
    return line


def phase_cacqr(dev, seed: int, m: int = CQR_M, n: int = CQR_N) -> dict:
    import jax
    import jax.numpy as jnp

    from capital_tpu.models import qr
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.utils.residual import tolerance

    dtype = jnp.float32
    grid = Grid.square(c=1, devices=[dev])
    cfg = qr.CacqrConfig(num_iter=2, mode="pallas")
    A = jax.jit(lambda k: jax.random.normal(k, (m, n), dtype))(
        jax.random.key(seed))
    exe, compile_s = _timed_compile(lambda a: qr.factor(grid, a, cfg), A)
    (Q, R), run_s = _timed_run(exe, A)
    orth, res = _qr_gates(A, Q, R)
    tol = tolerance(dtype)
    line = {"phase": "cacqr", "m": m, "n": n, "dtype": "float32",
            "mode": "pallas", "compile_s": compile_s, "run_s": run_s,
            "orthogonality": orth, "residual": res, "tol": tol,
            "tpu_custom_calls": _custom_calls(exe)}
    _check(orth < tol and res < tol, f"cacqr gates {orth:.3e}/{res:.3e}")
    return line


def _serve_cfg():
    from capital_tpu.serve import ServeConfig

    return ServeConfig(
        buckets=(16, 32, 64, 512), rows_buckets=(64, 128, 256, 2048),
        nrhs_buckets=(1, 4), max_batch=4, max_delay_s=0.005,
    )


def _serve_work(seed: int, ns=SMALL_NS + (MID_N,)) -> list:
    """posv at every n (3 each), lstsq (m = 4n) at every n (2 each): >= 16
    f32 requests over 3 small-N buckets and one mid-n bucket."""
    import numpy as np

    rng = np.random.default_rng(seed)
    work = []
    for n in ns:
        for k in (1, 4, 1):
            M = rng.standard_normal((n, n))
            A = M @ M.T / n + 3.0 * np.eye(n)
            work.append(("posv", A.astype(np.float32),
                         rng.standard_normal((n, k)).astype(np.float32)))
        for k in (1, 4):
            work.append(("lstsq",
                         rng.standard_normal((4 * n, n)).astype(np.float32),
                         rng.standard_normal((4 * n, k)).astype(np.float32)))
    return work


def _reference_error(op: str, A, B, x) -> float:
    """‖x − x_ref‖/‖x_ref‖ against the NumPy f64 solve of the same op."""
    import numpy as np

    A, B = np.asarray(A, np.float64), np.asarray(B, np.float64)
    ref = (np.linalg.solve(A, B) if op == "posv"
           else np.linalg.lstsq(A, B, rcond=None)[0])
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def _answer_tol(op: str) -> float:
    import jax.numpy as jnp

    from capital_tpu.utils.residual import tolerance

    # the normal-equations route squares the conditioning: 10x, as the
    # serve smoke gates lstsq
    return tolerance(jnp.float32) * (10 if op == "lstsq" else 1)


def phase_serve(dev, seed: int, ns=SMALL_NS + (MID_N,)) -> dict:
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.serve import SolveEngine

    eng = SolveEngine(grid=Grid.square(c=1, devices=[dev]), cfg=_serve_cfg())
    work = _serve_work(seed, ns)
    t0 = time.perf_counter()
    eng.warmup((op, A.shape, B.shape, "float32") for op, A, B in work)
    compile_s = time.perf_counter() - t0
    run_s: list[float] = []
    worst: dict[str, float] = {}
    for _ in range(2):  # the first pass also loads each program onto the chip
        t0 = time.perf_counter()
        tickets = [eng.submit(op, A, B) for op, A, B in work]
        eng.drain()
        responses = [t.result() for t in tickets]
        run_s.append(time.perf_counter() - t0)
        for (op, A, B), r in zip(work, responses):
            _check(r.ok and r.x is not None,
                   f"serve {op} {A.shape}: {r.error}")
            err = _reference_error(op, A, B, r.x)
            worst[op] = max(worst.get(op, 0.0), err)
            _check(err < _answer_tol(op),
                   f"serve {op} {A.shape}: error {err:.3e} vs numpy f64")
    small, custom = set(), 0
    for key, exe in eng.cache.items():
        op, _, a_shape = key[1][:3]
        if key[0] == "batch" and a_shape[-1] <= 128:
            calls = _custom_calls(exe)
            _check(calls > 0, f"serve bucket {key[1][:3]}: no tpu_custom_call")
            small.add(a_shape[-1])
            custom += calls
    _check(len(small) >= 3, f"serve: small-N buckets {sorted(small)} < 3")
    stats = eng.cache_stats()
    _check(stats["misses"] == 0, f"serve: recompiles after warmup {stats}")
    return {"phase": "serve", "requests": len(work), "dtype": "float32",
            "small_n_buckets": sorted(small), "mid_n": max(ns),
            "compile_s": compile_s, "run_s": run_s[0], "run2_s": run_s[1],
            "max_error_vs_f64": worst, "tpu_custom_calls": custom,
            "compiles": stats["compiles"]}


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def _shard_devices(x) -> list[int]:
    return sorted({s.device.id for s in x.addressable_shards})


def phase_cholinv_mesh(devs, seed: int, n: int = CHOLINV_N) -> dict:
    """Explicit-SUMMA cholinv on a 2x2x1 grid vs the same matrix factored
    by the one-chip pallas path on devs[0]."""
    import jax
    import jax.numpy as jnp

    from capital_tpu.models import cholesky
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.utils.residual import spd_operand, tolerance

    dtype = jnp.bfloat16
    bc = cholesky.pick_base_case(n)
    mesh = Grid.square(c=1, devices=devs[:4])
    one = Grid.square(c=1, devices=devs[:1])
    A = spd_operand(n, dtype, seed)
    Am = jax.device_put(A, mesh.face_sharding())
    cfg_m = cholesky.CholinvConfig(base_case_dim=bc, mode="explicit")
    cfg_1 = cholesky.CholinvConfig(base_case_dim=bc, mode="pallas")
    exe, compile_s = _timed_compile(
        lambda a: cholesky.factor(mesh, a, cfg_m), Am)
    (R, Rinv), run_s = _timed_run(exe, Am)
    fr, ir = _chol_gates(Am, R, Rinv)
    exe1, _ = _timed_compile(lambda a: cholesky.factor(one, a, cfg_1), A)
    R1, Rinv1 = exe1(A)
    f32 = jnp.float32
    diff = float(jax.jit(
        lambda r, r1: jnp.linalg.norm(r.astype(f32) - r1.astype(f32))
        / jnp.linalg.norm(r1.astype(f32)))(jax.device_put(R, devs[0]), R1))
    tol = tolerance(dtype)
    line = {"phase": "cholinv_mesh", "grid": "2x2x1", "n": n, "bc": bc,
            "dtype": "bfloat16", "mode": "explicit",
            "compile_s": compile_s, "run_s": run_s,
            "factor_resid": fr, "inverse_resid": ir,
            "rel_diff_vs_one_chip": diff, "tol": tol,
            "devices": _shard_devices(R)}
    _check(line["devices"] == sorted(d.id for d in devs[:4]),
           f"cholinv_mesh: R lives on {line['devices']}")
    _check(fr < tol and ir < tol, f"cholinv_mesh residuals {fr:.3e}/{ir:.3e}")
    _check(diff < tol, f"cholinv_mesh vs one chip: {diff:.3e}")
    return line


def phase_cacqr_sharded(devs, seed: int, m: int = CQR_M,
                        n: int = CQR_N) -> dict:
    """CQR2 with rows sharded over 4 chips vs the one-chip factor."""
    import jax
    import jax.numpy as jnp

    from capital_tpu.models import qr
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.utils.residual import tolerance

    dtype = jnp.float32
    flat = Grid.flat(devs[:4])
    one = Grid.square(c=1, devices=devs[:1])
    cfg = qr.CacqrConfig(num_iter=2, regime="1d", mode="pallas")
    A = jax.jit(lambda k: jax.random.normal(k, (m, n), dtype))(
        jax.random.key(seed))
    As = jax.device_put(A, flat.rows_sharding())
    exe, compile_s = _timed_compile(lambda a: qr.factor(flat, a, cfg), As)
    (Q, R), run_s = _timed_run(exe, As)
    orth, res = _qr_gates(As, Q, R)
    exe1, _ = _timed_compile(lambda a: qr.factor(one, a, cfg), A)
    _, R1 = exe1(A)
    diff = float(jnp.linalg.norm(jax.device_put(R, devs[0]) - R1)
                 / jnp.linalg.norm(R1))
    tol = tolerance(dtype)
    line = {"phase": "cacqr_sharded", "m": m, "n": n, "chips": 4,
            "dtype": "float32", "compile_s": compile_s, "run_s": run_s,
            "orthogonality": orth, "residual": res,
            "rel_diff_R_vs_one_chip": diff, "tol": tol,
            "devices": _shard_devices(Q),
            "tpu_custom_calls": _custom_calls(exe)}
    _check(line["devices"] == sorted(d.id for d in devs[:4]),
           f"cacqr_sharded: Q lives on {line['devices']}")
    _check(orth < tol and res < tol,
           f"cacqr_sharded gates {orth:.3e}/{res:.3e}")
    _check(diff < tol, f"cacqr_sharded vs one chip: {diff:.3e}")
    return line


def phase_router(devs, seed: int, ns=(SMALL_NS[1], MID_N)) -> dict:
    """A Router over 4 in-process replicas, replica i pinned to devs[i],
    answering a few posv/lstsq requests (one small-N, one mid-n size)."""
    from concurrent.futures import ThreadPoolExecutor

    from capital_tpu.serve import Router, RouterConfig, make_replica

    router = Router(RouterConfig(policy="least_loaded"))
    reps = [router.add_replica(make_replica("thread", f"r{i}", _serve_cfg(),
                                            device=i))
            for i in range(4)]
    try:
        work = _serve_work(seed, ns)
        specs = [(op, A.shape, B.shape, "float32") for op, A, B in work]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(reps)) as pool:  # compiles overlap
            placed = list(pool.map(
                lambda rep: rep.warmup(specs, timeout=900.0), reps))
        compile_s = time.perf_counter() - t0
        for i, info in enumerate(placed):
            _check(info is not None and info["device"] == devs[i].id,
                   f"router: replica r{i} warmed on {info}")
        run_s: list[float] = []
        served: dict[str, int] = {}
        worst: dict[str, float] = {}
        for _ in range(2):  # the first pass also loads programs onto chips
            t0 = time.perf_counter()
            tickets = [router.submit(op, A, B) for op, A, B in work]
            router.drain(timeout=600.0)
            results = [t.result(timeout=60.0) for t in tickets]
            run_s.append(time.perf_counter() - t0)
            for (op, A, B), r in zip(work, results):
                _check(r.ok, f"router {op} {A.shape}: {r.error}")
                i = int(r.replica_id[1:])
                _check(r.devices == (devs[i].id,),
                       f"router: {r.replica_id} answered from {r.devices}")
                served[r.replica_id] = served.get(r.replica_id, 0) + 1
                err = _reference_error(op, A, B, r.x)
                worst[op] = max(worst.get(op, 0.0), err)
                _check(err < _answer_tol(op),
                       f"router {op} {A.shape}: error {err:.3e} vs numpy f64")
        _check(len(served) >= 2, f"router: load landed only on {served}")
    finally:
        router.stop()
    return {"phase": "router", "replicas": 4, "requests": len(work),
            "served_per_replica": served,
            "replica_devices": [info["device"] for info in placed],
            "compile_s": compile_s, "run_s": run_s[0], "run2_s": run_s[1],
            "max_error_vs_f64": worst}


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, HERE)
    import capital_tpu  # an ImportError here means no package next to us

    if not os.path.abspath(capital_tpu.__file__).startswith(HERE + os.sep):
        print(f"chip_smoke: capital_tpu comes from {capital_tpu.__file__}, "
              f"not from {HERE}", file=sys.stderr)
        return 1
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    from capital_tpu.utils import compile_cache

    compile_cache.enable()
    if args.chips == 1:
        phases = [lambda: phase_cholinv(devs[0], args.seed),
                  lambda: phase_cacqr(devs[0], args.seed),
                  lambda: phase_serve(devs[0], args.seed)]
    else:
        phases = [lambda: phase_cholinv_mesh(devs, args.seed),
                  lambda: phase_cacqr_sharded(devs, args.seed),
                  lambda: phase_router(devs, args.seed)]
    ok = True
    for run in phases:
        try:
            line = dict(run(), ok=True)
        except SmokeFailure as e:
            line, ok = {"ok": False, "error": str(e)}, False
        print(json.dumps(line), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
