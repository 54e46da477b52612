"""serve_closed_loop: a SolveEngine under a closed loop of clients.

Each client holds one request: it submits, waits until the response lands,
then submits the next request of the pool (the pool is cycled in the seed's
order).  The loop is copied from capital_tpu/serve/loadgen.run_closed_loop,
with a time limit and each request timed on the client's side, from just
before ``submit`` to the first poll that sees its response.

* solves_per_s: responses that landed ok inside the window, over the window.
* latency_p95_ms: nearest-rank p95 over every request submitted in the
  window (those in flight at the close are waited for).
* counters: the engine Collector's batch occupancies over the window and the
  per-request queue waits of the window's responses.

Set-up makes the pool on the host, compiles (or loads) every bucket program
the pool reaches, runs a batch of every occupancy in each, then runs the
whole pool through the same loop until a pass builds no program, so that
every program has run on the chip and every operand shape, occupancy and
batch slot has been staged before the window opens.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

import common


def _engine(ctx, control: bool):
    from capital_tpu.parallel.topology import Grid
    from capital_tpu.serve import ServeConfig, SolveEngine

    s = dict(ctx.config["serve"])
    if control:  # the program's own lower-precision path
        s.update(ctx.config["control"])
    for k, v in s.items():
        if isinstance(v, list):
            s[k] = tuple(v)
    cfg = ServeConfig(**s)  # its programs persist in JAX's cache
    return SolveEngine(grid=Grid.square(c=1, devices=ctx.devices[:1]),
                       cfg=cfg)


@dataclasses.dataclass
class _Loop:
    eng: object
    pool: list
    clients: int
    ctx: object
    done: list = dataclasses.field(default_factory=list)
    sent: int = 0

    def run(self, seconds=None, requests=None):
        """Until `seconds` have passed or `requests` have been submitted,
        then until every submitted request has landed.  Returns the wall
        time at the close."""
        eng, ctx, pool = self.eng, self.ctx, self.pool
        t0 = time.perf_counter()
        stop = self.sent + requests if requests is not None else None
        out: list = []  # (ticket, pool index, t_submit, sequence)
        closed_at = None

        def collect():
            nonlocal out
            now = time.perf_counter()
            still = []
            for item in out:
                if item[0].response is not None:
                    self.done.append((item[1], item[2], now,
                                      item[0].response, item[3]))
                else:
                    still.append(item)
            moved = len(still) < len(out)
            out = still
            return moved

        while True:
            if closed_at is None:
                now = time.perf_counter()
                if (seconds is not None and now - t0 >= seconds) or (
                        stop is not None and self.sent >= stop):
                    closed_at = now - t0
            if closed_at is None:
                while len(out) < self.clients and (
                        stop is None or self.sent < stop):
                    idx = self.sent % len(pool)
                    op, A, B = pool[idx]
                    ts = time.perf_counter()
                    with ctx.span("submit"):
                        t = eng.submit(op, A, B)
                    out.append((t, idx, ts, self.sent))
                    self.sent += 1
            with ctx.span("pump"):
                eng.pump()
            if collect():
                continue
            if closed_at is not None and not out:
                return closed_at, t0
            waiting = [item for item in out if item[0].done]
            if waiting:
                with ctx.span("block"):
                    waiting[0][0].result()
                collect()
            elif closed_at is not None or not eng.queue_depth():
                with ctx.span("drain"):
                    eng.drain()
                collect()
            else:
                time.sleep(min(eng.cfg.max_delay_s, 1e-3))


def run(ctx, control: bool = False) -> common.Outcome:
    gen = common.generator_module()
    tr = ctx.traffic
    pool = gen.solve_pool(tr, ctx.seed)
    marks = [("pool", common.elapsed(ctx.t_process))]
    eng = _engine(ctx, control)
    eng.warmup({(op, A.shape, B.shape, str(A.dtype)) for op, A, B in pool})
    marks.append(("buckets", common.elapsed(ctx.t_process)))
    _every_occupancy(eng, pool)
    marks.append(("occupancies", common.elapsed(ctx.t_process)))
    loop = _Loop(eng, pool, int(tr["clients"]), ctx)
    for k in range(3):  # every staging shape, until a pass builds nothing
        before = common.Builds.now()
        loop.run(requests=len(pool))
        built = common.Builds.since(before)["requests"]
        marks.append((f"pass{k}:{built}", common.elapsed(ctx.t_process)))
        if not built:
            break
    print("benchmark: set-up marks " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks), file=sys.stderr)
    first = len(loop.done)
    built = common.Builds.now()
    occ0 = len(eng.stats.occupancies)
    seq0 = loop.sent
    setup_s = common.elapsed(ctx.t_process)
    with ctx.window():
        wall, t0 = loop.run(seconds=ctx.seconds)
    builds = common.Builds.since(built)
    mem = common.memory_peak(ctx.devices)
    win = [d for d in loop.done[first:] if d[4] >= seq0]
    close = t0 + wall
    answered = sum(1 for d in win if d[3].ok and d[2] <= close)
    failed = sum(1 for d in win if not d[3].ok)
    lat = [(d[2] - d[1]) * 1e3 for d in win]
    waits = [d[3].queue_wait_s for d in win if d[3].queue_wait_s is not None]
    counters = {
        "occupancies": list(eng.stats.occupancies[occ0:]),
        "queue_waits_s": waits,
        "builds": builds,
    }
    checks = _check(ctx, pool, win)
    return common.Outcome(
        setup_s=setup_s, attempted=len(win), failed=failed,
        e2e={"setup_s": setup_s, "solves_per_s": answered / wall,
             "latency_p95_ms": common.percentile(lat, 95)},
        counters=counters, checks=checks, memory_peak_bytes=mem)


def _every_occupancy(eng, pool) -> None:
    """Run a batch of every occupancy, 1 to capacity, in every bucket the
    pool reaches: the engine's batch assembly and landing build one eager
    program per bucket, occupancy and slot."""
    from capital_tpu.serve import batching

    seen = set()
    for op, A, B in pool:
        b = batching.bucket_for(op, A.shape, B.shape, str(A.dtype), eng.cfg)
        if b is None or b.key in seen:
            continue
        seen.add(b.key)
        for k in range(1, b.capacity + 1):
            tickets = [eng.submit(op, A, B) for _ in range(k)]
            eng.drain()
            for t in tickets:
                t.result()


def _check(ctx, pool, win) -> dict:
    """Compare a sample of the window's answers, drawn from the seed and
    holding the largest request of each op, with the reference."""
    k = int(ctx.workload["check_sample"])
    rng = np.random.default_rng(common.mix(ctx.seed, 7))
    pick = set(rng.choice(len(win), size=min(k, len(win)), replace=False)
               .tolist())
    for op in {pool[d[0]][0] for d in win}:
        biggest = max((i for i, d in enumerate(win) if pool[d[0]][0] == op),
                      key=lambda i: pool[win[i][0]][1].size)
        pick.add(biggest)
    refs: dict = {}
    gaps: dict = {}
    for i in sorted(pick):
        idx, _, _, resp, _ = win[i]
        op, A, B = pool[idx]
        name = f"{op}_gap"
        if not resp.ok or resp.x is None:
            gaps[name] = float("inf")
            continue
        if idx not in refs:
            refs[idx] = ctx.reference.solve(op, A, B)
        gaps[name] = max(gaps.get(name, 0.0),
                         common.relgap(np.asarray(resp.x), refs[idx]))
    limits = ctx.workload["limits"]
    return {k: (v, float(limits[k])) for k, v in sorted(gaps.items())}
