"""Generator ``open_loop``: vehicle clients asking the policy server for
decisions on a fixed, seeded schedule.

The system under test is ``repro.serve``: requests enter a
``MicroBatchQueue`` as they fall due and ``ServeEngine.decide`` answers the
next batch (up to the largest bucket) whenever the queue is not empty.
Arrivals are one merged Poisson stream at ``rate_per_s`` over the fleet's
``clients`` (the arithmetic of ``repro.serve.queue.poisson_arrivals``,
copied here), each request a fresh standard-normal observation. The loop is
open: a request falls due at its time whether or not earlier ones are
answered.

Set-up draws the served weights on the device from the seed, builds the
engine (one compiled program per bucket), serves ``warmup_s`` seconds of
the schedule through a queue of their own and collects the heap. The
window is the next ``--seconds`` of the schedule, through a fresh queue;
after it closes, requests due in it are still answered, for up to
``close_wait_s`` seconds.

``decision_p99_ms`` and ``decision_p90_ms``: the 99th and the 90th
percentile, over every request due in the window, of the time from when it
was due to when its decision was back on the host; a request never
answered counts with the time waited for it. A cell reports the ones that
``BENCHMARK.json`` lists for it (see PERF.md on the host's stalls).

``correct``: every decision answered for the window is compared with the
plain reference (``fedbench.reference.serve``) on the same observation.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from fedbench.harness import Check, CompileCounter, Outcome, memory_peak, seed_key
from fedbench.reference import serve as ref


def arrivals(rate: float, horizon: float, rng) -> np.ndarray:
    """Poisson arrival times on [0, horizon): exponential gaps drawn in
    chunks of the expected count until past the horizon."""
    expected = max(16, int(rate * horizon * 1.2))
    chunks, t = [], 0.0
    while t < horizon:
        chunk = t + np.cumsum(rng.exponential(1.0 / rate, size=expected))
        chunks.append(chunk)
        t = float(chunk[-1])
    times = np.concatenate(chunks)
    return times[times < horizon]


def schedule(cfg: dict, traffic: dict, seed: int, seconds: float):
    """``(times, client_ids, obs)`` of the whole run, warm-up included."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    times = arrivals(traffic["rate_per_s"], traffic["warmup_s"] + seconds, rng)
    ids = rng.integers(0, cfg["serve"]["clients"], size=times.shape[0])
    obs = rng.standard_normal(
        (times.shape[0], cfg["policy"]["obs_dim"])).astype(np.float32)
    return times, ids, obs


class OpenLoop:
    """Serves a schedule through the queue and the engine, in one thread."""

    def __init__(self, engine, queue, times, ids, obs, span):
        from repro.serve import ObsRequest

        self.engine, self.queue = engine, queue
        self.times, self.ids, self.obs = times, ids, obs
        self.request = ObsRequest
        self.done_at = np.full(times.shape[0], np.nan)
        self.actions = np.full(times.shape[0], np.nan, np.float32)
        self.next = 0
        self.t0 = None
        self.decide_s = []
        self.max_gap_s = 0.0
        self.span = span

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def run(self, until: float, drain_to: int = 0, deadline: float = 0.0):
        """Serve until schedule time ``until``; then, while any of the first
        ``drain_to`` requests is unanswered, keep serving up to ``deadline``."""
        span = self.span
        if self.t0 is None:
            self.t0 = time.perf_counter()
        times, n = self.times, self.times.shape[0]
        owed = int(np.isnan(self.done_at[:drain_to]).sum())
        last = self.now()
        while True:
            now = self.now()
            self.max_gap_s = max(self.max_gap_s, now - last)
            last = now
            if now >= until and (owed == 0 or now >= deadline):
                return
            j = int(np.searchsorted(times, now, side="right"))
            if j > self.next:
                with span("admit"):
                    push = self.queue.push
                    for r in range(self.next, j):
                        push(self.request(int(self.ids[r]), float(times[r]),
                                          self.obs[r]))
                self.next = j
            batch = self.queue.next_batch()
            if batch is None:
                target = (times[self.next] if self.next < n
                          else until if now < until else deadline)
                with span("wait"):
                    while self.now() < target:
                        pass
                continue
            obs, reqs = batch
            t1 = time.perf_counter()
            with span("decide"):
                act = self.engine.decide(obs)
            t2 = time.perf_counter()
            self.decide_s.append((t1 - self.t0, t2 - t1))
            seqs = np.fromiter((r.seq for r in reqs), np.int64, len(reqs))
            k = min(len(act), len(seqs))
            self.done_at[seqs[:k]] = t2 - self.t0
            self.actions[seqs[:k]] = np.asarray(act)[:k, 0]
            owed -= int((seqs[:k] < drain_to).sum())


def run(ctx) -> Outcome:
    import jax

    from repro.serve import MicroBatchQueue, ServeEngine

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    serve, wait = cfg["serve"], traffic["close_wait_s"]
    t0 = time.perf_counter()
    times, ids, obs = schedule(cfg, traffic, ctx.seed, ctx.seconds)
    pi = jax.jit(lambda k: ref.make_pi(k, cfg["policy"]))(seed_key(ctx.seed))
    t_sched = time.perf_counter()
    engine = ServeEngine({"pi": pi}, buckets=tuple(serve["buckets"]),
                         mode=serve["mode"], backend=cfg["backend"])
    t_engine = time.perf_counter()

    def loop_over(part, t_start):
        """The requests of ``part``, due from ``t_start`` of the schedule."""
        queue = MicroBatchQueue(engine.max_batch(), engine.obs_dim)
        return OpenLoop(engine, queue, times[part] - t_start, ids[part],
                        obs[part], ctx.span)

    w0 = traffic["warmup_s"]
    lo = int(np.searchsorted(times, w0))
    warm = loop_over(slice(0, lo), 0.0)
    warm.run(until=w0, drain_to=lo, deadline=w0 + wait)
    # The window starts from a collected heap, as a server's does after its
    # warm-up; see PERF.md on the collector's pauses.
    gc.collect()
    setup_s = ctx.setup_s()
    ctx.note("setup", schedule_s=f"{t_sched - t0:.3f}",
             engine_s=f"{t_engine - t_sched:.3f}",
             warmup_s=f"{time.perf_counter() - t_engine:.3f}",
             setup_s=f"{setup_s:.3f}", requests=int(times.shape[0]))
    window = slice(lo, times.shape[0])
    loop = loop_over(window, w0)
    n = times.shape[0] - lo
    dec0, pad0 = engine.n_decisions, engine.n_padded
    gc_log = []
    cb = lambda phase, info: gc_log.append((phase, info["generation"]))
    gc.callbacks.append(cb)
    try:
        with CompileCounter() as compiles, ctx.window():
            loop.run(until=ctx.seconds, drain_to=n,
                     deadline=ctx.seconds + wait)
    finally:
        gc.callbacks.remove(cb)
    answered = ~np.isnan(loop.done_at)
    lat_ms = 1e3 * (np.where(answered, loop.done_at, loop.now()) - loop.times)
    p90, p99 = (float(x) for x in np.percentile(lat_ms, [90, 99]))
    decide_ms = [1e3 * d for t, d in loop.decide_s if t < ctx.seconds]
    n_dec = engine.n_decisions - dec0
    n_pad = engine.n_padded - pad0
    peak = memory_peak(ctx.devices)
    starts = [g for p, g in gc_log if p == "start"]
    ctx.note("window", requests=n, unanswered=int((~answered).sum()),
             p50_ms=f"{np.percentile(lat_ms, 50):.4f}", p90_ms=f"{p90:.4f}",
             p99_ms=f"{p99:.4f}",
             max_ms=f"{lat_ms.max():.3f}",
             max_loop_gap_ms=f"{1e3 * loop.max_gap_s:.3f}",
             gc_collections=len(starts), gc_gen2=starts.count(2),
             decide_calls=len(decide_ms), compiles=compiles.count,
             memory_peak_bytes=peak)
    served = loop.actions[answered]
    del engine, warm
    t1 = time.perf_counter()
    expect = ref.decisions(pi, obs[window][answered])[:, 0]
    gap = float(np.max(np.abs(served - expect))) if served.size else 0.0
    ctx.note("reference", seconds=f"{time.perf_counter() - t1:.3f}",
             compared=int(served.size))
    lim = cfg["limits"]["open_loop"]
    checks = [Check("decision_gap", gap, lim["decision_gap"]),
              Check("unanswered", float((~answered).sum()), 0.0),
              Check("window_compiles", float(compiles.count), 0.0)]
    return Outcome(
        attempted=n, failed=int((~answered).sum()),
        setup_s=setup_s,
        end_to_end={"decision_p99_ms": p99, "decision_p90_ms": p90},
        checks=checks,
        memory_peak_bytes=peak,
        layer={"decide_ms": decide_ms, "decisions": n_dec, "padded": n_pad,
               "chips": len(ctx.devices)})
