"""A serving cell: set-up, an open-loop window of requests, the check.

Set-up makes the weights from the seed (``bench/weights.py``, as for the
train cells), a pool of users by the configuration family's traffic
generator that the mix names under ``users``, and each user's hidden state
through the program's own backbone (``models/api.py::backbone_hidden``).
It then starts ONE ``ServingEngine`` over ``engine.make_decode_fn`` on the
dense head (``index=None``), which compiles every bucket shape, and drives
it through a short warm-up schedule.  Last, it collects the heap and
freezes what is alive (``gc.freeze``), so that a collection inside the
window walks the window's own objects and not every module, jaxpr and
array of set-up; the window logs each generation's pauses.
The engine holds each request up to the mix's ``deadline_ms``, as long as
the client waits for it: a request that a stall of the host made late is
answered late, and judged by its answer.

The window replays a schedule drawn from the seed: ``rate x seconds``
arrivals at times uniform over the window (a Poisson process with its
count fixed, so every seed offers the same work in another order), each a
user drawn from the pool.  One client thread submits each request when it
is due, and times it from its SCHEDULED arrival to its result: a client
that falls behind shows as latency, not as a lower offered rate.  Once
the schedule has run, the client waits for every answer, up to
``DRAIN_SECONDS`` past the close.

The check: the engine is stopped and the program's state freed; then the
float32 reference (``bench/serve_reference.py``) scores a seeded sample of
the window's requests over the whole table, from weights it makes anew
from the seed, and the returned ids and logits are compared with it.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench import compare, families, serve_reference, weights
from bench.harness import TRACE_DIR
from bench.kinds.train import arch_config
from bench.peaks import peak_for
from bench.tracing import capture

#: purposes of the keys folded from the run's seed (the weights' purpose
#: is the train cells' too)
K_WEIGHTS, K_USERS = 1, 2
#: salts of the schedules and of the checked sample drawn from the seed
S_WARM, S_WINDOW, S_SAMPLE = 1, 2, 3
#: seconds of the warm-up schedule, at the cell's rate, in set-up
WARM_SECONDS = 0.5
#: how long past the window's close the client waits for answers
DRAIN_SECONDS = 60.0
#: the counters of ServingEngine.counters() whose deltas a window reports
COUNTERS = ("microbatches", "batch_slots", "batch_real", "completed",
            "expired", "failed")


def percentile(values, p: float) -> float:
    """The nearest-rank p-th percentile: the smallest value with at least
    p% of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if not v.size:
        return 0.0
    return float(v[max(0, math.ceil(p / 100.0 * v.size) - 1)])


def schedule(seed: int, salt: int, rate: float, seconds: float,
             pool: int) -> tuple[np.ndarray, np.ndarray]:
    """(arrival times in s from the window's start, sorted; user of each):
    round(rate x seconds) arrivals uniform over the window, users uniform
    over the pool, both from the seed."""
    seed = int(seed) % 2 ** 64
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n)), rng.integers(0, pool, n)


def replay(engine, pool: np.ndarray, times, users,
           clock=time.perf_counter, sleep=time.sleep) -> dict:
    """Submit request i to ``engine`` at ``times[i]`` s after the start,
    every request that is due at once; returns the start (``t0``), each
    request's submission time (``t_submit``, s from the start), its future
    and the engine's queue depth once the last was submitted."""
    n = len(times)
    futures, t_submit = [None] * n, np.zeros(n)
    t0 = clock()
    i = 0
    while i < n:
        now = clock() - t0
        if times[i] > now:
            sleep(times[i] - now)
            continue
        with jax.profiler.TraceAnnotation("bench.submit"):
            while i < n and times[i] <= now:
                t_submit[i] = clock() - t0
                futures[i] = engine.submit(pool[users[i]])
                i += 1
    return {"t0": t0, "t_submit": t_submit, "futures": futures,
            "queue_at_close": engine.counters()["queue_depth"]}


def collect(sent: dict, times, k: int, drain_s: float = DRAIN_SECONDS,
            clock=time.perf_counter) -> dict:
    """Every answer of a replay, waiting at most ``drain_s`` past the
    close.  A request's latency is from its scheduled arrival to its
    result; one that never answered counts the time waited for it."""
    n = len(times)
    ids = np.full((n, k), -1, np.int32)
    logits = np.zeros((n, k), np.float32)
    ok = np.zeros(n, bool)
    lat = np.zeros(n)
    t0, t_submit = sent["t0"], sent["t_submit"]
    give_up = clock() + drain_s
    with jax.profiler.TraceAnnotation("bench.drain"):
        for i, fut in enumerate(sent["futures"]):
            late_ms = (t_submit[i] - times[i]) * 1e3
            try:
                r = fut.result_wait(max(give_up - clock(), 0.0))
            except TimeoutError:
                lat[i] = (clock() - t0 - times[i]) * 1e3
                continue
            lat[i] = late_ms + r.latency_ms
            if r.ok:
                ok[i] = True
                ids[i], logits[i] = r.ids, r.logits
    end = float(np.max(times + lat / 1e3)) if n else 0.0
    return {"ids": ids, "logits": logits, "ok": ok, "latency_ms": lat,
            "late_ms": (t_submit - times) * 1e3, "seconds": end,
            "queue_at_close": sent["queue_at_close"]}


class Setup:
    """The seed's weights and users, and the program's engine, started."""

    def __init__(self, cell, seed: int, log):
        from repro.models import api
        from repro.serve import engine as serve_engine
        from repro.serve.server import ServingEngine
        from repro.sharding.rules import local_ctx

        self.cfg, self.mix, self.seed = cell.config, cell.traffic, seed
        cfg, mix = self.cfg, self.mix
        arch, ctx = arch_config(cfg), local_ctx()
        base = weights.seed_key(seed)
        layout = jax.eval_shape(
            lambda: api.init_params(jax.random.PRNGKey(0), arch, ctx))
        t0 = time.perf_counter()
        self.params = weights.make_params(
            layout, jax.random.fold_in(base, K_WEIGHTS),
            cfg["init_embed_std"])
        users = families.load(cfg).ring(cfg, mix["users"],
                                        jax.random.fold_in(base, K_USERS))
        hidden = jax.jit(lambda p, b: api.backbone_hidden(p, b, arch,
                                                          ctx)[0])
        self.pool = np.concatenate([np.asarray(hidden(self.params, b))
                                    for b in users])
        del users
        t1 = time.perf_counter()
        self.k = int(mix["k"])
        decode = serve_engine.make_decode_fn(
            arch, ctx, api.head_table(self.params, arch), self.k)
        self.engine = ServingEngine(
            decode, d_model=self.pool.shape[1], k=self.k,
            buckets=tuple(mix["buckets"]), max_wait_ms=mix["max_wait_ms"],
            default_deadline_ms=mix["deadline_ms"],
            cache_size=mix["cache_size"]).start(warmup=True)
        t2 = time.perf_counter()
        self.window(S_WARM, WARM_SECONDS)
        t3 = time.perf_counter()
        gc.collect()
        gc.freeze()
        log(f"[setup] weights and {len(self.pool)} users {t1 - t0:.2f} s, "
            f"engine {t2 - t1:.2f} s, warm-up schedule {t3 - t2:.2f} s, "
            f"heap frozen {time.perf_counter() - t3:.2f} s")

    def counters(self) -> dict:
        c = self.engine.counters()
        return {name: c[name] for name in COUNTERS}

    def window(self, salt: int, seconds: float,
               rate: float | None = None) -> dict:
        """One replay of the schedule at ``rate`` (the mix's by default),
        and every answer; with the counters' deltas over it."""
        rate = self.mix["rate"] if rate is None else rate
        times, users = schedule(self.seed, salt, rate, seconds,
                                len(self.pool))
        before = self.counters()
        with GcPauses() as pauses, \
                jax.profiler.TraceAnnotation("bench.window"):
            sent = replay(self.engine, self.pool, times, users)
            got = collect(sent, times, self.k)
        after = self.counters()
        got.update(users=users, rate=rate, gc=pauses.summary(),
                   **{name: after[name] - before[name] for name in COUNTERS})
        return got

    def stop(self) -> None:
        """Stop the engine, and give set-up's heap back to the collector."""
        self.engine.stop()
        gc.unfreeze()


class GcPauses:
    """The garbage collector's pauses while in the block, by generation."""

    def __enter__(self):
        self.pauses, self._t = [], 0.0
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)

    def _note(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self) -> dict:
        """{generation: [collections, longest ms, total ms]}"""
        out = {}
        for g, s in self.pauses:
            n, top, total = out.get(g, [0, 0.0, 0.0])
            out[g] = [n + 1, max(top, s * 1e3), total + s * 1e3]
        return out


def summary(w: dict) -> dict:
    """What a window shows, for the log and the sweep."""
    n = len(w["ok"])
    return {"offered": n, "rate": w["rate"], "completed": int(w["ok"].sum()),
            "expired": w["expired"], "failed": w["failed"],
            "served_per_s": float(w["ok"].sum() / w["seconds"])
            if w["seconds"] else 0.0,
            "p50_ms": percentile(w["latency_ms"], 50),
            "p90_ms": percentile(w["latency_ms"], 90),
            "p99_ms": percentile(w["latency_ms"], 99),
            "late_p90_ms": percentile(w["late_ms"], 90),
            "late_max_ms": float(np.max(w["late_ms"])) if n else 0.0,
            "queue_at_close": w["queue_at_close"],
            "gc_pauses": w["gc"],
            "microbatches": w["microbatches"],
            "batch_fill": (w["batch_real"] / w["batch_slots"]
                           if w["batch_slots"] else 0.0)}


def sample(seed: int, w: dict, size: int) -> np.ndarray:
    """The seeded sample of a window's requests that the check scores."""
    seed = int(seed) % 2 ** 64
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, S_SAMPLE])
    n = len(w["ok"])
    return np.sort(rng.choice(n, min(size, n), replace=False))


def head_table(cell, seed: int):
    """The output table, made anew from the seed's weights."""
    from repro.models import api
    from repro.sharding.rules import local_ctx

    arch = arch_config(cell.config)
    layout = jax.eval_shape(lambda: api.init_params(
        jax.random.PRNGKey(0), arch, local_ctx()))
    key = jax.random.fold_in(weights.seed_key(seed), K_WEIGHTS)
    p = weights.make_params(layout, key, cell.config["init_embed_std"])
    return families.load(cell.config).head_table(p)


def check(cell, seed: int, h, ids, logits, log) -> tuple[dict, dict]:
    """(numbers, the reference's answers) for the returned ``ids`` and
    ``logits`` of requests whose hidden states are ``h``."""
    t0 = time.perf_counter()
    abs_mode = bool(cell.config.get("abs_softmax", False))
    w = head_table(cell, seed)
    k = ids.shape[1]
    ref = serve_reference.reference_topk(h, w, k, abs_mode)
    s_of = serve_reference.scores_of(h, w, ids, abs_mode)
    numbers = serve_reference.numbers(ids, logits, ref, s_of)
    log(f"[check] reference over {len(h)} requests x {w.shape[0]} rows in "
        f"{time.perf_counter() - t0:.1f} s; {int(ref['due'].sum())} of "
        f"{ref['due'].size} ids due; numbers {numbers}")
    return numbers, ref


def run(cell, args, env) -> dict:
    """Set-up, window, check; returns the result's fields."""
    log = env.log
    setup = Setup(cell, args.seed, log)
    traced = bool(args.trace)
    env.compiles.on = True
    t_setup_end = time.time()
    if traced:
        with capture(str(cell.root / TRACE_DIR)):
            w = setup.window(S_WINDOW, args.seconds)
    else:
        w = setup.window(S_WINDOW, args.seconds)
    env.compiles.on = False
    setup.stop()
    peak = env.memory_peak()
    s = summary(w)
    log(f"[serve] window: {s}; compiles in window {env.compiles.count}, "
        f"peak device memory {peak} bytes")

    # the check: free the program's state, then the reference
    idx = sample(args.seed, w, cell.traffic["check_sample"])
    idx = idx[w["ok"][idx]]
    h = setup.pool[w["users"][idx]]
    n_table = int(cell.config["vocab_size"])
    d = int(setup.pool.shape[1])
    del setup
    gc.collect()
    numbers, _ = check(cell, args.seed, h, w["ids"][idx], w["logits"][idx],
                       log)
    correct, checks = compare.verdict(numbers, cell.checks)
    attempted = len(w["ok"])
    failed = attempted - int(w["ok"].sum())
    layer_input = {"kind": "serve", "seconds": w["seconds"], "n": n_table,
                   "d": d, "k": int(cell.traffic["k"]),
                   "table_itemsize": np.dtype(
                       cell.config["param_dtype"]).itemsize,
                   **{name: w[name] for name in COUNTERS}}
    if traced:
        layer_input["peak_bytes_per_s"] = peak_for(
            jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return {
        "correct": correct and failed == 0 and attempted > 0,
        "attempted": attempted, "failed": failed,
        "setup_end": t_setup_end, "peak": peak, "checks": checks,
        "e2e": {"serve_p90_ms": s["p90_ms"], "serve_p99_ms": s["p99_ms"]},
        "layer_input": layer_input,
    }


def control(cell, seed: int, log, cache: dict | None = None,
            detail: dict | None = None) -> dict:
    """The readings that set both ends of the limits, at the cell's own
    size and load, on one seed, each against the float32 reference on the
    same sampled requests: ``program``, the program's own answers from a
    window long enough to give the check its whole sample (the lower
    readings); then the reference put in the program's place, broken as
    ``serve_reference.control_answers`` says: ``control`` (the table in
    float8 e4m3), ``half_catalog``, ``ascending`` and, where the
    configuration ranks by |o|, ``raw_o``.  There, ``witness`` reads the
    program's own output function
    (``core/sampled_softmax.py::transform_logits``, the one its loss
    trains under) over h . W^T at the default precision against the
    reference, beside the program's served answers.  ``cache`` and
    ``detail`` are unused: each seed builds its own engine, whose
    programs the compile cache holds."""
    mix = cell.traffic
    setup = Setup(cell, seed, log)
    seconds = max(1.0, 1.25 * mix["check_sample"] / mix["rate"])
    w = setup.window(S_WINDOW, seconds)
    setup.stop()
    log(f"[control] program window: {summary(w)}")
    idx = sample(seed, w, mix["check_sample"])
    idx = idx[w["ok"][idx]]
    h = setup.pool[w["users"][idx]]
    del setup
    gc.collect()
    out = {}
    out["program"], ref = check(cell, seed, h, w["ids"][idx],
                                w["logits"][idx], log)
    abs_mode = bool(cell.config.get("abs_softmax", False))
    w_tab = head_table(cell, seed)
    k = int(mix["k"])
    modes = ("control", "half_catalog", "ascending") + (
        ("raw_o",) if abs_mode else ())
    for mode in modes:
        ids, logits = serve_reference.control_answers(h, w_tab, k, abs_mode,
                                                      mode)
        s_of = serve_reference.scores_of(h, w_tab, ids, abs_mode)
        out[mode] = serve_reference.numbers(ids, logits, ref, s_of)
        log(f"[control] {mode}: {out[mode]}")
    if abs_mode:
        ids, logits = _program_output_topk(h, w_tab, k)
        s_of = serve_reference.scores_of(h, w_tab, ids, abs_mode)
        out["witness"] = serve_reference.numbers(ids, logits, ref, s_of)
        log(f"[control] witness: {out['witness']}")
    return out


def _program_output_topk(h, w, k: int):
    """Top k of the program's own output function over h . W^T, at the
    default precision: what the model it trains ranks first."""
    from repro.core.sampled_softmax import transform_logits

    @jax.jit
    def top(hc, w):
        o = transform_logits(hc @ w.T, abs_mode=True)
        vals, ids = jax.lax.top_k(o, k)
        return ids, vals

    parts = [jax.device_get(top(h[i:i + 256], w))
             for i in range(0, len(h), 256)]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def sweep(cell, seed: int, rates, seconds: float, log) -> list[dict]:
    """One set-up, then a window at each offered rate: what each shows."""
    setup = Setup(cell, seed, log)
    out = []
    try:
        for i, rate in enumerate(rates):
            s = summary(setup.window(S_WINDOW + 10 * (i + 1), seconds,
                                     rate=rate))
            log(f"[sweep] {s}")
            out.append(s)
    finally:
        setup.stop()
    return out
