"""Multi-host mesh promotion on 8 forced host devices: a simulated 4-host
topology ("host", "data", "model"), the tuple-axis collective helpers, and
train steps — sync AND overlapped refresh — over the host axis."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import distributed
from repro.data.pipeline import batch_iterator_for
from repro.launch.mesh import make_multihost_mesh
from repro.optim import make_optimizer
from repro.sharding.rules import mesh_ctx
from repro.train.loop import fit

# ---- topology ---------------------------------------------------------------
mesh = make_multihost_mesh(hosts=4)  # 8 devices / 4 hosts -> 2 per host
assert mesh.axis_names == ("host", "data", "model")
assert mesh.shape["host"] == 4 and mesh.shape["model"] == 2, dict(mesh.shape)
ctx = mesh_ctx(mesh)
assert ctx.data_axes == ("host", "data"), ctx.data_axes
assert ctx.tp == 2
print("topology:", dict(mesh.shape), "data_axes:", ctx.data_axes)

# ---- tuple-axis collective helpers ------------------------------------------
AXES = ("host", "data", "model")


def probe():
    idx = distributed.axis_index(AXES)
    n = distributed.axis_size(AXES)
    off = distributed.local_vocab_offset(10, AXES)
    return jnp.stack([idx, n, off]).reshape(1, 3)


out = np.asarray(jax.shard_map(probe, mesh=mesh, in_specs=(),
                           out_specs=P(AXES, None))())
assert out.shape == (8, 3), out.shape
# composed index enumerates devices row-major over (host, data, model)
np.testing.assert_array_equal(out[:, 0], np.arange(8))
np.testing.assert_array_equal(out[:, 1], np.full(8, 8))
np.testing.assert_array_equal(out[:, 2], np.arange(8) * 10)
print("tuple-axis helpers ok")

# ---- train: sync refresh over the host axis ---------------------------------
cfg = get_config("youtube-dnn").reduced(
    vocab_size=256, m_negatives=32, sampler_block=32,
    tower_dims=(64, 32), user_feature_dim=64, history_len=3)
opt = make_optimizer("adamw", 1e-2, weight_decay=0.0)
data = batch_iterator_for(cfg, ctx, global_batch=16, seq_len=0, seed=0)
res = fit(cfg, ctx, opt, data, steps=8, log_every=0, max_len=8)
assert np.all(np.isfinite(res.losses)), res.losses
print("sync multihost losses:", [f"{x:.3f}" for x in res.losses])

# ---- train: overlapped refresh island over the host axis --------------------
import dataclasses  # noqa: E402

cfg_o = dataclasses.replace(cfg, refresh_mode="overlap",
                            sampler_refresh_every=3, refresh_stale_steps=1)
data_o = batch_iterator_for(cfg_o, ctx, global_batch=16, seq_len=0, seed=0)
res_o = fit(cfg_o, ctx, opt, data_o, steps=9, log_every=0, max_len=8)
assert np.all(np.isfinite(res_o.losses)), res_o.losses
assert res_o.refresh_swaps > 0, res_o.refresh_swaps
print("overlap multihost losses:", [f"{x:.3f}" for x in res_o.losses],
      "swaps:", res_o.refresh_swaps,
      "staleness:", res_o.refresh_staleness)

print("MULTIHOST MESH CHECKS PASSED")
