"""Multi-pod dry-run (deliverable e) + the multi-host collective gate.

For every (architecture x input-shape x mesh) cell this lowers + compiles
the real jitted step (train_step for train shapes, prefill/decode steps for
serving shapes) against ShapeDtypeStruct inputs with production shardings —
no allocation — then records:

  * compiled.memory_analysis()  — per-device argument/temp/peak bytes,
  * compiled.cost_analysis()    — per-device HLO FLOPs & bytes accessed,
  * the collective schedule     — parsed from compiled.as_text(): op counts
    and operand bytes for all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute,

into experiments/dryrun/<arch>__<shape>__<mesh>.json, which §Roofline reads.

The collective-contract GATE (``--gate``) lowers the real train step for
EVERY estimator in the registry on a simulated 16-host
("host", "data", "model") mesh (``launch.hostsim`` forces the virtual
device farm; ``launch.mesh.make_multihost_mesh`` slices it into hosts) and
asserts the named-collective ops, device-group sizes and operand shapes
against the documented contract (DESIGN.md §7) via
``launch.hlo_analysis.check_collective_contract`` — the cross-host
promotion of ``core/distributed.py`` is CI-checkable without real hosts.

The forced device count is applied lazily via
``hostsim.ensure_host_platform_devices`` (NOT an import-time XLA_FLAGS
clobber): jax locks the count at first backend init, so the old
module-level assignment was silently inert under pytest (backend already
live → 1-device mesh) and destroyed unrelated XLA_FLAGS.  The helper
guards the first-init constraint with a pointed error and is idempotent,
so the gate can run twice in one process.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --gate [--gate-hosts 16]
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, shapes_for
from repro.launch.hostsim import ensure_host_platform_devices
from repro.launch.mesh import make_multihost_mesh, make_production_mesh
from repro.models import api
from repro.optim import make_optimizer
from repro.serve.engine import (
    abstract_decode_inputs,
    abstract_prefill_inputs,
    make_decode_step,
    make_prefill_step,
)
from repro.sharding.rules import ctx_for_serve, ctx_for_train
from repro.train.step import abstract_train_state, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

#: archs where Adam moments would not fit HBM — use factored second moments
ADAFACTOR_THRESHOLD = 15e9


def _param_count(cfg, ctx) -> int:
    struct = jax.eval_shape(
        lambda k: api.init_params(k, cfg, ctx, max_len=128),
        jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(struct))


def pick_optimizer(cfg, ctx):
    n = _param_count(cfg, ctx)
    name = "adafactor" if n > ADAFACTOR_THRESHOLD else "adamw"
    return make_optimizer(name, 1e-4), name, n


def analyze(lowered, compiled, mesh) -> dict:
    from repro.launch.hlo_analysis import analyze_hlo

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    corrected = analyze_hlo(txt)  # trip-count-aware (scan bodies x trips)
    return {
        "devices": int(np.prod(list(mesh.shape.values()))),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
        },
        "cost": {
            # raw XLA numbers (while bodies counted ONCE — kept for reference)
            "flops_per_device_raw": float(ca.get("flops", 0.0)),
            "bytes_accessed_per_device_raw": float(
                ca.get("bytes accessed", 0.0)),
            # trip-corrected (the numbers §Roofline uses)
            "flops_per_device": float(corrected["flops"]),
            "bytes_per_device": float(corrected["bytes"]),
        },
        "collectives": corrected["collectives"],
        "structural_bytes_per_device": int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + 2 * ma.temp_size_in_bytes),
        "hlo_instructions": txt.count("\n"),
    }


# --------------------------------------------------------------------------
# cell lowering
# --------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    meta: dict = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "seq_len": shape.seq_len, "global_batch": shape.global_batch,
                  "kind": shape.kind}

    with mesh:
        if shape.kind == "train":
            ctx = ctx_for_train(mesh, cfg)
            meta["sharding"] = ctx.mode
            opt, opt_name, n_params = pick_optimizer(cfg, ctx)
            meta["optimizer"] = opt_name
            meta["params"] = n_params
            state_sds = abstract_train_state(cfg, ctx, opt,
                                             max_len=shape.seq_len)
            batch_specs = api.train_batch_specs(cfg, shape.global_batch,
                                                shape.seq_len)
            dsp = ctx.data_axes if len(ctx.data_axes) > 1 else \
                ctx.data_axes[0]
            batch_sds = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=NamedSharding(
                        mesh, ctx.fit_spec(
                            s.shape,
                            P(dsp, *([None] * (len(s.shape) - 1)))))),
                batch_specs)
            key_sds = jax.ShapeDtypeStruct(
                (2,), jnp.uint32, sharding=NamedSharding(mesh, P(None)))
            step_fn = make_train_step(cfg, ctx, opt)
            t0 = time.time()
            lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(
                state_sds, batch_sds, key_sds)
        elif shape.kind == "prefill":
            ctx = ctx_for_serve(mesh, cfg)
            meta["sharding"] = ctx.mode
            params_sds, batch_sds = abstract_prefill_inputs(
                cfg, ctx, shape.global_batch, shape.seq_len)
            meta["params"] = sum(
                int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(params_sds))
            step_fn = make_prefill_step(cfg, ctx, max_len=shape.seq_len)
            t0 = time.time()
            lowered = jax.jit(step_fn).lower(params_sds, batch_sds)
        else:  # decode
            ctx = ctx_for_serve(mesh, cfg)
            meta["sharding"] = ctx.mode
            params_sds, tok_sds, cache_sds, pos_sds = abstract_decode_inputs(
                cfg, ctx, shape.global_batch, shape.seq_len)
            meta["params"] = sum(
                int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(params_sds))
            step_fn = make_decode_step(cfg, ctx)
            t0 = time.time()
            lowered = jax.jit(step_fn, donate_argnums=(2,)).lower(
                params_sds, tok_sds, cache_sds, pos_sds)
        meta["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        meta["compile_s"] = round(time.time() - t1, 1)
    return lowered, compiled, mesh, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str) -> dict:
    lowered, compiled, mesh, meta = lower_cell(arch, shape_name, multi_pod)
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis() or {}
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    rec = {**meta, **analyze(lowered, compiled, mesh)}
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir,
                      f"{arch}__{shape_name}__{meta['mesh']}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    mem_gib = rec["memory"]["peak_bytes"] / 2**30
    arg_gib = rec["memory"]["argument_bytes"] / 2**30
    tf = rec["cost"]["flops_per_device"] / 1e12
    print(f"[dryrun] {arch:18s} {shape_name:12s} {meta['mesh']:8s} OK  "
          f"peak {mem_gib:6.2f} GiB  args {arg_gib:6.2f} GiB  "
          f"{tf:8.2f} TF/dev  lower {meta['lower_s']}s "
          f"compile {meta['compile_s']}s", flush=True)
    return rec


# --------------------------------------------------------------------------
# 16-host collective-contract gate
# --------------------------------------------------------------------------

GATE_HOSTS = 16
GATE_PER_HOST = 2
GATE_BATCH = 32
#: samplers whose carried statistics ride the island (beyond the base
#: config's block family, which every estimator cell already compiles):
#: the quantized multi-index must keep the same collective schedule — its
#: codebook stats are shard-local, so sampling adds NO collectives.
GATE_SAMPLERS = ("midx",)


def _gate_cfg():
    """Tiny recsys cell: every collective of the full train step (head Fd
    gather, model-axis loss psums/pmax, host-axis reductions) at a
    CI-friendly compile time."""
    return get_config("youtube-dnn").reduced(
        vocab_size=256, m_negatives=32, sampler_block=32,
        tower_dims=(64, 32), user_feature_dim=64, history_len=3)


def gate_contract(cfg, ctx, est_name: str) -> list[dict]:
    """The documented collective contract for one estimator on a
    ("host", "data", "model") mesh (DESIGN.md §7 table).

    shard_map lowers the island's lax collectives manually, so the op
    kinds, replica-group sizes and (post-SPMD, shard-local) operand shapes
    below are stable across XLA versions:

      * head Fd all-gather — the (v_l, d/fsdp) head shard's feature dim
        gathered over the data axes (outermost = the host axis), result
        (v_l, d) per model shard;
      * model-axis psums — (T_l,)-shaped add-all-reduces over tp-sized
        groups (positive logit + estimator partition terms);
      * model-axis pmax — max-all-reduce over tp-sized groups (global
        logsumexp shift) for the softmax-family estimators;
      * host/data-axis psum — the loss-sum reduction across the full
        data extent (hosts x per-host data), scalar add-all-reduce.
    """
    from repro.models.transformer import padded_vocab

    tp = ctx.tp
    data_ext = 1
    for a in ctx.data_axes:
        data_ext *= ctx.mesh.shape[a]
    v_l = padded_vocab(cfg, tp) // tp
    d = api.hidden_width(cfg)
    t_l = GATE_BATCH // data_ext  # recsys: tokens == batch rows
    softmax_family = est_name in ("sampled-softmax", "full")
    contract = [
        {"op": "all-gather", "group_size": ctx.mesh.shape[ctx.data_axes[0]],
         "dims": [v_l, d], "dtype": "f32"},
        {"op": "all-reduce", "group_size": tp, "dims": [t_l],
         "dtype": "f32", "reduce": "add"},
        {"op": "all-reduce", "group_size": data_ext, "reduce": "add"},
    ]
    if softmax_family:
        contract.append({"op": "all-reduce", "group_size": tp,
                         "dims": [t_l], "reduce": "max"})
    return contract


def run_gate(hosts: int = GATE_HOSTS, per_host: int = GATE_PER_HOST,
             out_dir: str | None = None) -> dict:
    """Lower the train step for EVERY registry estimator — plus each
    ``GATE_SAMPLERS`` family under the default estimator — on a simulated
    ``hosts``-host mesh and assert the collective contract.  Returns the
    per-cell record (also written to ``out_dir`` when given); raises
    SystemExit(1) on any violation."""
    import dataclasses

    from repro.core.estimators import estimator_names
    from repro.launch.hlo_analysis import (
        check_collective_contract,
        collective_ops,
    )

    ensure_host_platform_devices(hosts * per_host)
    mesh = make_multihost_mesh(hosts=hosts)
    # The contract's per-shard token shape is GATE_BATCH / (hosts x dp);
    # a non-divisible topology would silently floor it and every estimator
    # would then "fail" the contract with confusing shape mismatches —
    # reject the invocation up front, before any lowering.
    data_ext = mesh.shape["host"] * mesh.shape["data"]
    if GATE_BATCH % data_ext:
        raise SystemExit(
            f"[gate] invalid topology: the gate batch ({GATE_BATCH} rows) "
            f"does not divide over the mesh data extent {data_ext} "
            f"(= hosts {mesh.shape['host']} x per-host data "
            f"{mesh.shape['data']}; per-host (dp, tp) is derived from "
            f"--gate-per-host={per_host} by mesh_shape_for).  Pick "
            f"--gate-hosts/--gate-per-host so hosts x dp divides "
            f"{GATE_BATCH}.")
    base = _gate_cfg()
    report: dict = {"mesh": dict(mesh.shape), "estimators": {},
                    "samplers": {}}
    violations: list[str] = []

    def lower_gate_cell(cfg):
        with mesh:
            ctx = ctx_for_train(mesh, cfg)
            opt = make_optimizer("adamw", 1e-4)
            state_sds = abstract_train_state(cfg, ctx, opt, max_len=8)
            batch_specs = api.train_batch_specs(cfg, GATE_BATCH, 0)
            dsp = ctx.data_axes if len(ctx.data_axes) > 1 else \
                ctx.data_axes[0]
            batch_sds = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=NamedSharding(
                        mesh, ctx.fit_spec(
                            s.shape,
                            P(dsp, *([None] * (len(s.shape) - 1)))))),
                batch_specs)
            key_sds = jax.ShapeDtypeStruct(
                (2,), jnp.uint32, sharding=NamedSharding(mesh, P(None)))
            step_fn = make_train_step(cfg, ctx, opt)
            t0 = time.time()
            compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
                state_sds, batch_sds, key_sds).compile()
        return compiled.as_text(), ctx, round(time.time() - t0, 1)

    def check_gate_cell(section, label, cfg):
        txt, ctx, compile_s = lower_gate_cell(cfg)
        errs = check_collective_contract(
            txt, gate_contract(cfg, ctx, cfg.estimator))
        colls = collective_ops(txt)
        report[section][label] = {
            "compile_s": compile_s,
            "collectives": sorted(
                {f"{c['op']}@{c['group_size']}"
                 f"{c['dims']}:{c['reduce'] or c['dtype']}" for c in colls}),
            "violations": errs,
        }
        status = "OK" if not errs else "CONTRACT VIOLATION"
        print(f"[gate] {label:18s} {status} "
              f"({len(colls)} collective ops, {compile_s}s)", flush=True)
        for e in errs:
            print(f"       - {e}", flush=True)
        violations.extend(f"{label}: {e}" for e in errs)

    for est in estimator_names():
        check_gate_cell("estimators", est,
                        dataclasses.replace(base, name=f"{base.name}-{est}",
                                            estimator=est))
    # sampler dimension: families with island-carried stats must compile on
    # the multi-host mesh WITHOUT changing the collective schedule
    for smp in GATE_SAMPLERS:
        check_gate_cell("samplers", smp,
                        dataclasses.replace(base, name=f"{base.name}-{smp}",
                                            sampler=smp, sampler_block=32))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "collective_gate.json"), "w") as f:
            json.dump(report, f, indent=1)
    hshape = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    if violations:
        print(f"[gate] FAILED on {hshape}: {len(violations)} violation(s)")
        raise SystemExit(1)
    print(f"[gate] PASSED: collective contract holds for estimators "
          f"{list(report['estimators'])} + samplers "
          f"{list(report['samplers'])} on the {hshape} "
          f"(host, data, model) mesh")
    return report


def cells(mesh_sel: str):
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if mesh_sel in ("single", "both"):
                yield arch, shape.name, False
            if mesh_sel in ("multi", "both"):
                yield arch, shape.name, True


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default=OUT_DIR)
    ap.add_argument("--gate", action="store_true",
                    help="run the simulated multi-host collective-contract "
                         "gate instead of dry-run cells")
    ap.add_argument("--gate-hosts", type=int, default=GATE_HOSTS)
    ap.add_argument("--gate-per-host", type=int, default=GATE_PER_HOST)
    args = ap.parse_args()

    if args.gate:
        run_gate(hosts=args.gate_hosts, per_host=args.gate_per_host,
                 out_dir=args.out)
        return

    # The production meshes below need 512 host placeholders; apply the
    # forced device count up front (fails loudly if jax already
    # initialized with a different count — see launch/hostsim.py).
    ensure_host_platform_devices(512)

    todo = []
    if args.all:
        todo = list(cells(args.mesh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        if args.mesh in ("single", "both"):
            todo.append((args.arch, args.shape, False))
        if args.mesh in ("multi", "both"):
            todo.append((args.arch, args.shape, True))

    failures = []
    for arch, shape, mp in todo:
        try:
            run_cell(arch, shape, mp, args.out)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape, mp, repr(e)))
            print(f"[dryrun] {arch} {shape} "
                  f"{'2x16x16' if mp else '16x16'} FAILED: {e}", flush=True)
            traceback.print_exc()
    print(f"\n[dryrun] done: {len(todo) - len(failures)}/{len(todo)} cells "
          f"passed")
    for f in failures:
        print("  FAILED:", f)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
