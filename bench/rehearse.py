"""Compile rehearsal: a train cell's timed step and its reference's
gradient program compiled for one chip of a described TPU v5e, without
the chip.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse --workload <cell>

Prints the compiler's ``memory_analysis`` per program: the bytes of
arguments, outputs, aliases and temporaries on one chip.  Nothing runs,
so nothing here is a time.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

from bench import harness

GiB = 1024 ** 3


def describe(compiled) -> str:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return (f"arguments {ma.argument_size_in_bytes / GiB:.3f} GiB, outputs "
            f"{ma.output_size_in_bytes / GiB:.3f}, aliased "
            f"{ma.alias_size_in_bytes / GiB:.3f}, temporaries "
            f"{ma.temp_size_in_bytes / GiB:.3f}; total {total / GiB:.3f} GiB "
            f"({total} bytes)")


def _on(sharding, tree):
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def rehearse_train(cell, sharding, modes=("fp32",)) -> None:
    import jax
    import jax.numpy as jnp

    from bench import families, reference
    from bench.kinds import train
    from repro.core.samplers import sampler_from_config
    from repro.models import api
    from repro.optim import make_optimizer
    from repro.sharding.rules import local_ctx
    from repro.train.step import TrainState, make_train_step

    cfg, mix = cell.config, cell.traffic
    arch = train.arch_config(cfg)
    ctx = local_ctx()
    o = train.optimizer_settings(cfg)
    opt = make_optimizer("adamw", o["lr"], b1=o["b1"], b2=o["b2"],
                         eps=o["eps"], weight_decay=o["weight_decay"])
    layout = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), arch, ctx))
    sampler = sampler_from_config(arch)
    shapes = sampler.state_shapes(arch, 1)
    state = TrainState(params=layout, opt_state=jax.eval_shape(opt.init,
                                                               layout),
                       sampler_state=shapes,
                       step=jax.ShapeDtypeStruct((), jnp.int32))
    batch = jax.eval_shape(lambda k: train.make_ring(cfg, mix, k)[0],
                           jax.random.PRNGKey(0))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state, batch, key = (_on(sharding, t) for t in (state, batch, key))
    step = jax.jit(make_train_step(arch, ctx, opt), donate_argnums=(0,))
    print(f"[rehearse] {cell.name} train step, batch "
          f"{train.targets_per_batch(mix, cfg)} targets: "
          f"{describe(step.lower(state, batch, key).compile())}", flush=True)
    proj = None
    if cfg.get("sampler_proj_rank"):
        width = families.load(cfg).head_table(layout).shape[-1]
        proj = jax.ShapeDtypeStruct((cfg["sampler_proj_rank"], width),
                                    jnp.float32, sharding=sharding)
    p32 = _on(sharding, jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), layout))
    for mode in modes:
        vg = jax.jit(jax.value_and_grad(reference.make_loss(cfg, mode,
                                                            False)))
        print(f"[rehearse] {cell.name} reference gradient ({mode}): "
              f"{describe(vg.lower(p32, batch, key, proj).compile())}",
              flush=True)


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json")
    ap.add_argument("--modes", default="fp32",
                    help="comma-separated precisions of the reference "
                         "(fp32, bf16, fp8) whose gradient to compile")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root = pathlib.Path(root or ".").resolve()
    cell = harness.load_cell(root, args.workload)
    harness.add_program_path(root)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    rehearse_train(cell, SingleDeviceSharding(topo.devices[0]),
                   tuple(args.modes.split(",")))
    return 0

if __name__ == "__main__":
    sys.exit(main())
