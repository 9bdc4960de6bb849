"""Chip smoke test: the train -> sample -> serve path on one TPU chip.

Drives the system's main path once, through the entry points a user calls,
at the published widths of starcoder2-3b (d_model 3072, 24 heads of 128
with 2 KV heads, d_ff 12288, vocab 49152, layernorm + gelu + qkv bias).
Only the depth is cut: to the most layers whose train step the compiler
fits on the chip.  Weights and data are random, made from ``--seed``.

  1. device   the first JAX device must be a TPU; there is no fallback.
  2. train    a few steps of ``make_train_step`` with the configured
              sampler (block-quadratic-shared, m=2048 shared negatives).
  3. sampler  the paper's tree sampler (§3.2) over the trained head:
              per-example draws through the compiled ``block_scores`` and
              ``leaf_scores`` kernels, every draw's logq checked against
              the dense ``all_class_logq`` oracle, then the eq. 2 loss.
  4. serve    a ``ServingEngine`` over ``make_decode_fn`` answers requests
              on the dense head, then on the exported retrieval index; the
              full beam must return the dense head's ids.

    python chip_smoke.py              # phases 1-4 on one chip
    python chip_smoke.py --chips 4    # only the vocab-sharded head island
                                      # on four chips, against one chip

The last line of stdout is one JSON object naming the device.  Any failure
raises, exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.api import SoftmaxHead  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import tree  # noqa: E402
from repro.data.pipeline import batch_iterator_for  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_mesh_for  # noqa: E402
from repro.models import api  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.serve import ServingEngine, make_decode_fn  # noqa: E402
from repro.sharding.rules import (  # noqa: E402
    ctx_for_train,
    local_ctx,
    param_specs_for,
)
from repro.train.step import (  # noqa: E402
    init_train_state,
    make_eval_fn,
    make_train_step,
)
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "starcoder2-3b"
#: depth search, deepest first.  The v5e compile rehearsal of the train
#: step (seq 2048, batch 1) needs 13.3 GiB at 7 layers and 14.4 GiB at 8.
MAX_LAYERS, MIN_LAYERS = 8, 4
#: device bytes left free beside the train step's own (allocator slack,
#: the data pipeline, the hidden states kept for the later phases)
HEADROOM = 2 * 1024 ** 3
SEQ, BATCH, STEPS = 2048, 1, 5
#: tree-sampler draws: the (T, m) grid the descent samples per step
TREE_T, TREE_M = 256, 64
#: |logq - oracle| bound: the draw and the oracle sum the same fp32 terms in
#: different orders (the kernels on the VPU / MXU, the oracle through XLA),
#: worth ~1e-5 per tree level over 8 levels; a wrong branch or leaf is off
#: by 0.1 or more.
LOGQ_ATOL = 1e-3
SERVE_K, SERVE_REQUESTS = 10, 8
SHARDED_CHIPS, SHARDED_BATCH, SHARDED_STEPS = 4, 4, 3
#: sharded vs one-device eval loss, both in fp32 at highest precision: the
#: two differ in fp32 summation order (sharded matmuls, the per-shard
#: logsumexp combine), ~1e-6; a lost or doubled shard is off by 1e-2 or more
EVAL_RTOL = 1e-4

_GiB = 1024 ** 3


def _optimizer():
    return make_optimizer("adamw", 1e-4)


def _step_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return f"{peak / _GiB:.2f} GiB" if peak is not None else "not reported"


# --- 1. device ---------------------------------------------------------------


def phase_device(chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# --- 2. train ----------------------------------------------------------------


def fit_depth(cfg, limit: int, *, seq: int = SEQ, batch: int = BATCH):
    """Compile the train step at MAX_LAYERS, MAX_LAYERS-1, ... layers and
    keep the deepest whose ``memory_analysis`` fits ``limit`` device bytes
    beside HEADROOM.  Returns (cfg at that depth, its compiled step)."""
    ctx, opt = local_ctx(), _optimizer()
    sds = jax.ShapeDtypeStruct
    batch_sds = {"tokens": sds((batch, seq), jnp.int32),
                 "labels": sds((batch, seq), jnp.int32)}
    key = jax.random.PRNGKey(0)
    for layers in range(MAX_LAYERS, MIN_LAYERS - 1, -1):
        c = dataclasses.replace(cfg, n_layers=layers)
        state_sds = jax.eval_shape(
            lambda k: init_train_state(k, c, ctx, opt, max_len=seq), key)
        compiled = jax.jit(make_train_step(c, ctx, opt),
                           donate_argnums=(0,)).lower(
                               state_sds, batch_sds, key).compile()
        need = _step_bytes(compiled)
        fits = need + HEADROOM <= limit
        print(f"[train] {layers} layers: step needs {need / _GiB:.2f} GiB "
              f"of {limit / _GiB:.2f} GiB -> "
              f"{'keep' if fits else 'too deep'}", flush=True)
        if fits:
            return c, compiled
    raise RuntimeError(f"{MIN_LAYERS} layers of {cfg.name} do not fit")


def phase_train(cfg, seed: int, *, step=None, seq: int = SEQ,
                batch: int = BATCH, steps: int = STEPS) -> dict:
    """A few train steps; returns the trained head table and the hidden
    states and labels of one more batch, for the phases after it (the
    train state itself is dropped here, freeing the chip)."""
    ctx, opt = local_ctx(), _optimizer()
    if step is None:
        step = jax.jit(make_train_step(cfg, ctx, opt), donate_argnums=(0,))
    data = batch_iterator_for(cfg, ctx, batch, seq, seed=seed)
    state = init_train_state(jax.random.PRNGKey(seed), cfg, ctx, opt,
                             max_len=seq)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"[train] {cfg.name}: {cfg.n_layers} of 30 layers, "
          f"{n_params / 1e9:.3f}B params, sampler={cfg.sampler} "
          f"m={cfg.m_negatives}, batch {batch}x{seq}", flush=True)
    losses, times = [], []
    for i in range(steps):
        b = next(data)
        t0 = time.perf_counter()
        state, metrics = step(state, b, jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), i))
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    print(f"[train] losses {[round(x, 4) for x in losses]}", flush=True)
    print(f"[train] step seconds {[round(t, 3) for t in times]} "
          f"(informative)", flush=True)
    print(f"[train] peak device memory {_peak_bytes(jax.devices()[0])}",
          flush=True)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite train loss: {losses}")
    hidden = jax.jit(lambda p, b: api.backbone_hidden(p, b, cfg, ctx)[:2])
    h, labels = hidden(state.params, next(data))
    head = jnp.copy(api.head_table(state.params, cfg))
    del state
    return {"head": head, "h": h, "labels": labels, "losses": losses}


# --- 3. the paper's sampler --------------------------------------------------


def phase_sampler(cfg, trained: dict, seed: int, *, t: int = TREE_T,
                  m: int = TREE_M) -> dict:
    """Per-example tree-sampler draws over the trained head, checked
    against the dense oracle, and the eq. 2 loss on them."""
    tcfg = dataclasses.replace(cfg, sampler="tree-quadratic", m_negatives=m)
    head = SoftmaxHead(tcfg)
    w, h, labels = trained["head"], trained["h"][:t], trained["labels"][:t]
    k_init, k_draw = jax.random.split(jax.random.PRNGKey(seed + 2))
    n_valid = jnp.asarray(tcfg.vocab_size, jnp.int32)
    sampler = head.sampler

    def oracle(state, hh, ids):
        rt = sampler.hydrate(state, n_valid)
        return tree.all_class_logq(rt["stats"], sampler.kernel, hh,
                                   rt["proj"])[ids]

    with jax.default_matmul_precision("highest"):
        state = jax.jit(head.init)(k_init, w)
        sample = jax.jit(head.sample).lower(state, h, k_draw).compile()
        n_kernels = sample.as_text().count("tpu_custom_call")
        ids, logq = sample(state, h, k_draw)
        want = jax.jit(jax.vmap(oracle, in_axes=(None, 0, 0)))(state, h, ids)
        loss = jax.jit(lambda w_, h_, l_, s_, k_: head.loss(
            w_, h_, l_, state=s_, key=k_))(w, h, labels, state, k_draw)
    ids, logq, want = map(np.asarray, (ids, logq, want))
    err = float(np.max(np.abs(logq - want)))
    impl = ops.resolve_fused_impl(tcfg.head_impl, *w.shape)
    print(f"[sampler] tree-quadratic: T={t} x m={m} draws, leaf "
          f"{tcfg.sampler_block}, rank {tcfg.sampler_proj_rank}, "
          f"{n_kernels} Pallas kernels in the draw program", flush=True)
    print(f"[sampler] max |logq - oracle| = {err:.3g} (atol {LOGQ_ATOL}); "
          f"eq. 2 loss mean {float(jnp.mean(loss)):.4f}; fused_head_lse "
          f"impl: {impl}", flush=True)
    if jax.default_backend() == "tpu" and n_kernels < 2:
        raise RuntimeError("the tree draw did not run the Pallas kernels")
    if not (np.all((ids >= 0) & (ids < tcfg.vocab_size))
            and np.all(np.isfinite(logq))):
        raise RuntimeError("tree draws out of range or with non-finite logq")
    if not err <= LOGQ_ATOL:
        raise RuntimeError(f"tree logq differs from the oracle by {err}")
    if not np.all(np.isfinite(np.asarray(loss))):
        raise RuntimeError("non-finite eq. 2 loss")
    return {"logq_err": err, "impl": impl}


# --- 4. serve ----------------------------------------------------------------


def _serve_all(eng: ServingEngine, queries: np.ndarray) -> list:
    futures = [eng.submit(q) for q in queries]
    results = [f.result_wait(900.0) for f in futures]
    bad = [r.error for r in results if not r.ok]
    if bad:
        raise RuntimeError(f"{len(bad)} requests failed: {bad[:3]}")
    return results


def _same_topk(a, b, tol: float = 1e-5) -> bool:
    """Equal ids and logits, except that neighbours whose logits tie to
    fp32 rounding may swap (their order is then the summation order's)."""
    if not np.allclose(a.logits, b.logits, rtol=tol, atol=tol):
        return False
    tie = np.zeros(a.ids.shape, bool)
    close = np.isclose(a.logits[1:], a.logits[:-1], rtol=tol, atol=tol)
    tie[1:] |= close
    tie[:-1] |= close
    return bool(np.all((a.ids == b.ids) | tie))


def phase_serve(cfg, trained: dict, *, requests: int = SERVE_REQUESTS
                ) -> dict:
    """Requests through the engine on the dense head, then on the
    exported index (full beam); both must agree."""
    w = trained["head"]
    queries = np.asarray(trained["h"][:requests], np.float32)
    head = SoftmaxHead(cfg)
    t0 = time.perf_counter()
    index = jax.block_until_ready(jax.jit(head.export_index)(w))
    build_s = time.perf_counter() - t0
    # Dense logits at full fp32 precision, like the index's exact leaf dots;
    # set globally because the engine's worker thread compiles the index
    # path.
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        eng = ServingEngine(make_decode_fn(cfg, local_ctx(), w, SERVE_K),
                            cfg.d_model, SERVE_K, buckets=(requests,),
                            max_wait_ms=50.0,
                            default_deadline_ms=900_000.0).start()
        try:
            dense = _serve_all(eng, queries)
            eng.swap_index(index)
            indexed = _serve_all(eng, queries)
            counters = eng.counters()
        finally:
            eng.stop()
    finally:
        jax.config.update("jax_default_matmul_precision", old)
    same = [_same_topk(a, b) for a, b in zip(dense, indexed)]
    print(f"[serve] index: {index.wq.shape[0]} leaves of "
          f"{index.leaf_size}, built in {build_s:.1f} s", flush=True)
    print(f"[serve] {len(dense)} dense + {len(indexed)} index requests ok; "
          f"full-beam ids == dense ids for {sum(same)}/{len(same)}; "
          f"p50 {counters['latency_ms']['p50']:.1f} ms (informative)",
          flush=True)
    if not all(r.index_version == 1 for r in indexed):
        raise RuntimeError("index requests were not served by the index")
    if not all(same):
        raise RuntimeError("full-beam index ids differ from the dense head")
    return {"requests": len(dense) + len(indexed)}


# --- --chips 4: the vocab-sharded head island --------------------------------


def phase_sharded(cfg, seed: int, *, chips: int = SHARDED_CHIPS,
                  seq: int = SEQ, batch: int = SHARDED_BATCH,
                  steps: int = SHARDED_STEPS) -> dict:
    """The full-softmax eval loss through the vocab-sharded head island
    equals the one-device loss on the same params and batch; then a few
    sharded train steps with stratified per-shard draws."""
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    mesh = make_mesh_for(chips)
    mctx, lctx = ctx_for_train(mesh, cfg), local_ctx()
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: api.init_params(k, cfg, lctx))(
            jax.random.PRNGKey(seed))
        b_local = next(batch_iterator_for(cfg, lctx, batch, seq, seed=seed))
        b_mesh = next(batch_iterator_for(cfg, mctx, batch, seq, seed=seed))
        ref = float(jax.jit(make_eval_fn(cfg, lctx))(params, b_local))
        params_s = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, param_specs_for(params, mctx))
        del params
        got = float(jax.jit(make_eval_fn(cfg, mctx))(params_s, b_mesh))
    del params_s
    print(f"[sharded] mesh {dict(mesh.shape)} ({cfg.train_sharding}), "
          f"{cfg.n_layers} layers fp32: eval loss sharded {got:.6f} vs one "
          f"device {ref:.6f} (rtol {EVAL_RTOL})", flush=True)
    if not np.isclose(got, ref, rtol=EVAL_RTOL, atol=0.0):
        raise RuntimeError(f"sharded eval loss {got} != one-device {ref}")

    opt = _optimizer()
    state = init_train_state(jax.random.PRNGKey(seed), cfg, mctx, opt,
                             max_len=seq)
    step = jax.jit(make_train_step(cfg, mctx, opt), donate_argnums=(0,))
    data = batch_iterator_for(cfg, mctx, batch, seq, seed=seed)
    losses = []
    for i in range(steps):
        state, metrics = step(state, next(data), jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), i))
        losses.append(float(metrics["loss"]))
    shards = sorted((s.device.id, s.data.shape) for s in
                    api.head_table(state.params, cfg).addressable_shards)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) / _GiB
              for d in mesh.devices.flat]
    print(f"[sharded] train losses {[round(x, 4) for x in losses]}; "
          f"sampler {cfg.sampler} m={cfg.m_negatives} "
          f"({cfg.m_negatives // mctx.tp} per vocab shard)", flush=True)
    print(f"[sharded] head shards (device, shape): {shards}", flush=True)
    print(f"[sharded] bytes in use per device (GiB): "
          f"{[round(x, 3) for x in in_use]}", flush=True)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite sharded train loss: {losses}")
    return {"eval_loss": got, "losses": losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()
    device = phase_device(args.chips)
    cfg = get_config(ARCH)
    if args.chips == SHARDED_CHIPS:
        phase_sharded(dataclasses.replace(cfg, n_layers=MIN_LAYERS),
                      args.seed)
    else:
        cfg, step = fit_depth(
            cfg, jax.devices()[0].memory_stats()["bytes_limit"])
        trained = phase_train(cfg, args.seed, step=step)
        phase_sampler(cfg, trained, args.seed)
        phase_serve(cfg, trained)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
