"""A training cell: set-up, a window of back-to-back steps, the check.

Set-up builds ONE object, the compiled ``make_train_step`` with its state,
drives it from the seed through its first two steps on distinct batches
(reading the first clipped gradient from AdamW's first moment and the
parameters' change after the two), and hands that same object to the
window.  The window runs steps back to back over a ring of batches made
on the device at set-up, with a few seconds of steps dispatched ahead;
once its time is up it sends nothing more, and every step sent counts over
the time until the last has finished.  After the window the program's state is freed and the float32
reference follows the same two steps from the same seed.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, families, flops, reference, weights
from bench.harness import TRACE_DIR
from bench.tracing import capture

#: the longest window traced in a --trace 1 run
TRACE_SECONDS = 5.0
#: seconds of steps the window keeps dispatched ahead of the one it waits
#: for
AHEAD_SECONDS = 4.0
#: purposes of the keys folded from the run's seed
K_WEIGHTS, K_TRAFFIC, K_STEPS, K_SAMPLER = 1, 2, 3, 4


def arch_config(cfg: dict):
    """The program's ArchConfig from a configuration file's keys."""
    from repro.configs.base import ArchConfig

    names = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cfg.items() if k in names}
    return ArchConfig(**kw)


def optimizer_settings(cfg: dict) -> dict:
    opt = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    opt.update(cfg["optimizer"])
    return opt


def make_ring(cfg: dict, mix: dict, key):
    """The mix's ring of batches, by the configuration's family."""
    return families.load(cfg).ring(cfg, mix, key)


def targets_per_batch(mix: dict, cfg: dict | None = None) -> int:
    """Softmax targets in one batch of ``mix``, by the configuration's
    family, or without one by the family that makes the mix's
    generator."""
    family = (families.load(cfg) if cfg is not None
              else families.with_generator(mix["generator"]))
    return family.targets_per_batch(cfg, mix)


class Inputs:
    """What the seed makes for a train cell: the weights (in the program's
    parameter layout), the sampler's projection, the ring of batches and
    the step keys.  The program and the reference are fed the same."""

    def __init__(self, cell, seed: int):
        from repro.models import api
        from repro.sharding.rules import local_ctx

        self.cfg, self.mix = cell.config, cell.traffic
        self.arch = arch = arch_config(self.cfg)
        base = weights.seed_key(seed)
        self.keys = {p: jax.random.fold_in(base, p)
                     for p in (K_WEIGHTS, K_TRAFFIC, K_STEPS, K_SAMPLER)}
        self.opt_cfg = optimizer_settings(self.cfg)
        self.layout = jax.eval_shape(
            lambda: api.init_params(jax.random.PRNGKey(0), arch,
                                    local_ctx()))

    def params0(self):
        return weights.make_params(self.layout, self.keys[K_WEIGHTS],
                                   self.cfg["init_embed_std"])

    def proj(self):
        r = self.cfg.get("sampler_proj_rank")
        if not r:
            return None
        d = families.load(self.cfg).head_table(self.layout).shape[-1]
        return jax.jit(lambda k: jax.random.normal(k, (r, d), jnp.float32)
                       / np.sqrt(r))(self.keys[K_SAMPLER])

    def ring(self):
        return make_ring(self.cfg, self.mix, self.keys[K_TRAFFIC])

    def step_keys(self):
        return list(jax.random.split(self.keys[K_STEPS], self.mix["ring"]))

    def reference(self, batches, keys, **kw) -> dict:
        """The reference's two steps from the seed's weights."""
        return reference.train_steps(self.params0(), batches, keys,
                                     self.cfg, self.opt_cfg,
                                     proj=self.proj(),
                                     remake_params0=self.params0, **kw)


class Setup:
    """The program's compiled step and state, built from the seed."""

    def __init__(self, inputs: Inputs, log, compiled=None):
        from repro.core.samplers import SamplerState, sampler_from_config
        from repro.optim import make_optimizer
        from repro.sharding.rules import local_ctx
        from repro.train.step import TrainState, make_train_step

        self.inputs = inp = inputs
        cfg, mix, arch, o = inp.cfg, inp.mix, inp.arch, inp.opt_cfg
        opt = make_optimizer("adamw", o["lr"], b1=o["b1"], b2=o["b2"],
                             eps=o["eps"], weight_decay=o["weight_decay"])
        t0 = time.perf_counter()
        params = jax.block_until_ready(inp.params0())
        t1 = time.perf_counter()
        self.ring = jax.block_until_ready(inp.ring())
        t2 = time.perf_counter()
        self.step_keys = inp.step_keys()
        proj = inp.proj()
        shapes = sampler_from_config(arch).state_shapes(arch, 1)
        state = TrainState(
            params=params, opt_state=jax.jit(opt.init)(params),
            sampler_state=SamplerState(
                stats=jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes.stats),
                const={} if proj is None else {"proj": proj}),
            step=jnp.zeros((), jnp.int32))
        self.compiled = compiled or jax.jit(
            make_train_step(arch, local_ctx(), opt),
            donate_argnums=(0,)).lower(state, self.ring[0],
                                       self.step_keys[0]).compile()
        log(f"[setup] weights {t1 - t0:.2f} s, batches {t2 - t1:.2f} s, "
            f"state and step {time.perf_counter() - t2:.2f} s")
        n_params = sum(math.prod(s.shape)
                       for s in jax.tree_util.tree_leaves(inp.layout))
        log(f"[train] {cfg['name']}: {n_params / 1e9:.4f}B params, "
            f"sampler {arch.sampler} m={arch.m_negatives}, batch "
            f"{targets_per_batch(mix, cfg)} targets, ring {mix['ring']}")
        self.state = state

    def first_steps(self, keep_params1: bool = False) -> dict:
        """Steps 1 and 2 through the compiled step; the program's side of
        the check, with ``grad1``, the first clipped gradient's leaves on
        the host.  ``keep_params1`` adds a host copy of the parameters
        after step 1 (``params1``)."""
        b1 = self.inputs.opt_cfg["b1"]
        p0 = jax.device_get(self.state.params)
        norms = jax.jit(reference.leaf_norms)
        losses = []
        self.state, met = self.compiled(self.state, self.ring[0],
                                        self.step_keys[0])
        losses.append(float(met["loss"]))
        first_m = jax.tree_util.tree_leaves(self.state.opt_state[-1]["m"])
        grad = np.asarray(norms(first_m)) / (1 - b1)
        grad1 = [np.asarray(_scaled(m, 1.0 / (1 - b1))) for m in first_m]
        params1 = (jax.device_get(self.state.params) if keep_params1
                   else None)
        t0 = time.perf_counter()
        self.state, met = self.compiled(self.state, self.ring[1],
                                        self.step_keys[1])
        losses.append(float(met["loss"]))
        self.step_seconds = time.perf_counter() - t0
        change = np.array([float(_change_norm(a, b)) for a, b in zip(
            jax.tree_util.tree_leaves(self.state.params),
            jax.tree_util.tree_leaves(p0))])
        out = {"loss": losses, "grad_norm": grad, "change_norm": change,
               "grad1": grad1}
        if keep_params1:
            out["params1"] = params1
        return out


@jax.jit
def _scaled(x, s):
    return x * s


@jax.jit
def _change_norm(now, before):
    """||now - before||, one leaf at a time on the device (``before``
    comes from the host, so only one leaf's copy is there at once)."""
    d = now.astype(jnp.float32) - before.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


def window(setup: Setup, seconds: float, first: int = 2) -> dict:
    """Steps back to back for ``seconds``, with about ``AHEAD_SECONDS`` of
    steps dispatched ahead of the one the host waits for, so that the chip
    stays fed while the host stalls.  When the time is up nothing more is
    sent; the window ends once every step sent has finished, and all of
    them count over all of that time."""
    ring, keys, step = setup.ring, setup.step_keys, setup.compiled
    n = len(ring)
    ahead = max(1, math.ceil(AHEAD_SECONDS / setup.step_seconds))
    losses, pending, i = [], collections.deque(), first
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("bench.batch_fetch"):
                batch, key = ring[i % n], keys[i % n]
            with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                setup.state, met = step(setup.state, batch, key)
            pending.append(met["loss"])
            i += 1
            if len(pending) > ahead:
                with jax.profiler.TraceAnnotation("bench.result_wait"):
                    losses.append(pending.popleft().block_until_ready())
        with jax.profiler.TraceAnnotation("bench.result_wait"):
            losses.extend(jax.block_until_ready(list(pending)))
            jax.block_until_ready(setup.state)
    t_end = time.perf_counter()
    losses = np.asarray(jax.device_get(losses), np.float64)
    return {"steps": len(losses), "seconds": t_end - t0, "ahead": ahead,
            "nonfinite": int(np.sum(~np.isfinite(losses)))}


def run(cell, args, env) -> dict:
    """Set-up, window, check; returns the result's fields."""
    log = env.log
    inputs = Inputs(cell, args.seed)
    setup = Setup(inputs, log)
    t0 = time.perf_counter()
    prog = setup.first_steps()
    log(f"[setup] first two steps and their readings "
        f"{time.perf_counter() - t0:.2f} s; losses {prog['loss']}")
    traced = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    env.compiles.on = True
    t_setup_end = time.time()
    if traced:
        with capture(str(cell.root / TRACE_DIR)):
            w = window(setup, seconds)
    else:
        w = window(setup, seconds)
    env.compiles.on = False
    peak = env.memory_peak()
    tpb = targets_per_batch(cell.traffic, cell.config)
    rate = w["steps"] * tpb / w["seconds"] if w["steps"] else 0.0
    log(f"[train] window: {w['steps']} steps ({w['ahead']} in flight) in "
        f"{w['seconds']:.3f} s, "
        f"{rate:.1f} targets/s, compiles in window {env.compiles.count}, "
        f"peak device memory {peak} bytes")

    # the check: free the program's state, then the reference
    batches, keys = setup.ring[:2], setup.step_keys[:2]
    hlo_text = setup.compiled.as_text() if traced else None
    del setup
    gc.collect()
    t_ref = time.perf_counter()
    ref = inputs.reference(batches, keys,
                           against={"program": prog.pop("grad1")})
    prog["grad_diff_norm"] = ref["diff_norm"]["program"]
    prog["rows_gap"] = ref["rows_gap"]["program"]
    log(f"[check] reference: losses {ref['loss']} in "
        f"{time.perf_counter() - t_ref:.1f} s")
    names = weights.leaf_names(inputs.layout)
    for i, name in enumerate(names):
        log(f"[check] leaf {name}: grad {prog['grad_norm'][i]:.6g} vs "
            f"{ref['grad_norm'][i]:.6g}, change {prog['change_norm'][i]:.6g}"
            f" vs {ref['change_norm'][i]:.6g}")
    numbers = compare.train_numbers(prog, ref)
    log(f"[check] numbers {numbers}")
    correct, checks = compare.verdict(numbers, cell.checks)
    fpt = flops.flops_per_target(cell.config, cell.traffic)
    return {
        "correct": correct and w["nonfinite"] == 0 and w["steps"] > 0,
        "attempted": w["steps"], "failed": w["nonfinite"],
        "setup_end": t_setup_end, "peak": peak, "checks": checks,
        "e2e": {"train_targets_per_s": rate},
        "layer_input": {"steps": w["steps"], "seconds": w["seconds"],
                        "flops_per_step": fpt * tpb, "kind": "train",
                        "hlo_text": hlo_text},
    }


def control(cell, seed: int, log, cache: dict | None = None,
            detail: dict | None = None) -> dict:
    """The readings that set both ends of the limits, at the cell's own
    size, on one seed, each against the float32 reference on the same
    inputs: ``program``, the program's own numbers (the lower readings);
    ``control``, the reference in the control's precision (float8) in the
    program's place; ``half_batch``, the reference with half of each batch
    left out; ``bf16``, the reference computing in bfloat16 as the
    configuration states, a second witness beside the program.
    ``witness`` sets the reference's loss at the program's own step-1
    parameters beside the program's step-2 loss and the reference's, and
    ``draw_noise`` the reference's first loss under other keys (other
    negatives) beside its own.  A state left unchanged reads 1 on
    ``grad_gap`` and ``change_gap`` by construction and needs no run.
    ``cache`` carries the compiled step from one seed to the next;
    ``detail``, where given, receives each run's per-leaf readings."""
    cache = {} if cache is None else cache
    inputs = Inputs(cell, seed)
    setup = Setup(inputs, log, cache.get("step"))
    cache["step"] = setup.compiled
    prog = setup.first_steps(keep_params1=True)
    params1 = prog.pop("params1")
    batches, keys = setup.ring[:2], setup.step_keys[:2]
    del setup
    gc.collect()
    runs, grads = {"program": prog}, {"program": prog.pop("grad1")}
    for name, kw in (("control", {"mode": "fp8"}),
                     ("half_batch", {"half": True}),
                     ("bf16", {"mode": "bf16"}),
                     ("reference", {"against": grads})):
        t0 = time.perf_counter()
        runs[name] = inputs.reference(batches, keys,
                                      keep_grad1=name != "reference", **kw)
        if name != "reference":
            grads[name] = runs[name].pop("grad1")
        log(f"[control] {name}: losses {runs[name]['loss']} in "
            f"{time.perf_counter() - t0:.1f} s")
    del grads
    ref = runs.pop("reference")
    names = weights.leaf_names(inputs.layout)
    out = {}
    for name, r in runs.items():
        r["grad_diff_norm"] = ref["diff_norm"][name]
        r["rows_gap"] = ref["rows_gap"][name]
        out[name] = compare.train_numbers(r, ref)
        log(f"[control] {name}: worst leaves "
            f"{compare.worst_leaves(r, ref, names)}")
        if detail is not None:
            detail[name] = {k: np.asarray(r[k]).tolist() for k in (
                "loss", "grad_norm", "change_norm", "grad_diff_norm")}
    if detail is not None:
        detail["reference"] = {k: np.asarray(ref[k]).tolist() for k in (
            "loss", "grad_norm", "change_norm")}
        detail["leaves"] = names
    at1 = reference.loss_at(params1, batches[1], keys[1], cell.config,
                            inputs.proj())
    del params1
    out["witness"] = {
        "program_loss2": prog["loss"][1], "reference_loss2": ref["loss"][1],
        "reference_loss2_at_program_params1": at1,
        "gap_at_program_params1": abs(prog["loss"][1] - at1) / abs(at1)}
    other = [jax.random.fold_in(keys[0], i) for i in range(1, 5)]
    losses = reference.losses_under_keys(inputs.params0(), batches[0],
                                         [keys[0]] + other, cell.config,
                                         inputs.proj())
    out["draw_noise"] = {
        "loss1_own_key": losses[0], "loss1_other_keys": losses[1:],
        "gap_other_keys": max(abs(x - losses[0]) / abs(losses[0])
                              for x in losses[1:])}
    return out
