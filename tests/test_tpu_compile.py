"""Every Pallas kernel the chip path reaches, compiled for a described TPU
v5e chip at the widths ``chip_smoke.py`` runs (starcoder2-3b: d_model 3072,
vocab 49152, sampler rank 64, leaf 512; serving leaf 4096; 24 query and 2
KV heads of 128 over 2048 tokens for the splash attention kernels).

Nothing runs: each test lowers the ops.py wrapper — its own tiling — for
one chip of a ``v5e:2x2`` topology and lets the TPU compiler accept or
refuse it, then checks that a Mosaic kernel is in the program.  Off the
chip ``ops._interpret()`` would pick interpret mode, so each test
patches it to compiled mode.  The topology is described inside a fixture
(never at import), and where it cannot be described the tests skip.
"""
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models import layers

D_MODEL = 3072
VOCAB = 49_152
RANK = 64          # sampler_proj_rank
LEAF = 512         # sampler_block (tree leaves, midx lists, rff leaves)
N_LEAVES = 128     # next_pow2(VOCAB / LEAF)
T, M = 256, 64     # chip_smoke's tree-sampler draws: T queries x m each
SERVE_LEAF = 4096  # retrieval.default_leaf_size(VOCAB, D_MODEL)
SERVE_T = 8        # largest serving bucket
SEQ, HEADS, KV_HEADS, HEAD_DIM = 2048, 24, 2, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_block_scores_compiles(one_chip, compiled_kernels):
    _compile(lambda h, z, c: ops.block_scores(h, z, c, alpha=100.0),
             _sds(one_chip, (T, RANK)),
             _sds(one_chip, (N_LEAVES, RANK, RANK)),
             _sds(one_chip, (N_LEAVES,)))


@pytest.mark.parametrize("r", [RANK, D_MODEL], ids=["tree", "midx"])
def test_leaf_scores_compiles(one_chip, compiled_kernels, r):
    _compile(lambda h, w, i: ops.leaf_scores(h, w, i, alpha=100.0),
             _sds(one_chip, (T, r)),
             _sds(one_chip, (N_LEAVES, LEAF, r)),
             _sds(one_chip, (T, M), jnp.int32))


def test_leaf_dots_compiles(one_chip, compiled_kernels):
    n_leaves = VOCAB // SERVE_LEAF
    n_leaves = 1 << (n_leaves - 1).bit_length()
    _compile(ops.leaf_dots,
             _sds(one_chip, (SERVE_T, D_MODEL)),
             _sds(one_chip, (n_leaves, SERVE_LEAF, D_MODEL)),
             _sds(one_chip, (SERVE_T, n_leaves), jnp.int32))


def test_midx_list_masses_compiles(one_chip, compiled_kernels):
    _compile(lambda h, c1, c2, codes, cnt: ops.midx_list_masses(
                 h, c1, c2, codes, cnt, alpha=100.0),
             _sds(one_chip, (T, D_MODEL)),
             _sds(one_chip, (16, D_MODEL)),
             _sds(one_chip, (16, D_MODEL)),
             _sds(one_chip, (N_LEAVES, 2), jnp.int32),
             _sds(one_chip, (N_LEAVES,)))


def test_rff_features_compiles(one_chip, compiled_kernels):
    _compile(lambda w, om, mask, s: ops.rff_features(w, om, mask, s,
                                                     tau=1.0),
             _sds(one_chip, (N_LEAVES, LEAF, D_MODEL)),
             _sds(one_chip, (128, D_MODEL)),
             _sds(one_chip, (N_LEAVES, LEAF)),
             _sds(one_chip, ()))


def test_fused_lse_fwd_bwd_compile(one_chip, compiled_kernels):
    """Forward and backward kernels, through the custom VJP, on the
    largest head shard the "auto" path sends to the kernel at d=3072."""
    n = 512
    assert ops.resolve_fused_impl("auto", n, D_MODEL) == "pallas"

    def loss(w, h, ids, corr):
        return jnp.sum(ops.fused_head_lse(w, h, ids, corr))

    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        _sds(one_chip, (n, D_MODEL)),
                        _sds(one_chip, (T, D_MODEL)),
                        _sds(one_chip, (T, 1 + M), jnp.int32),
                        _sds(one_chip, (T, 1 + M)))
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_fused_head_refuses_uncompilable_pallas():
    """At the full head the (n, d) accumulator cannot sit in VMEM: "auto"
    takes the chunked path and an explicit "pallas" is refused."""
    assert ops.resolve_fused_impl("auto", VOCAB, D_MODEL) == "chunked"
    with pytest.raises(ValueError, match="FUSED_HEAD_VMEM_BYTES"):
        ops.resolve_fused_impl("pallas", VOCAB, D_MODEL)


def test_splash_attention_fwd_bwd_compile_in_the_attention_scope(
        one_chip, compiled_kernels):
    """The kernel path of ``layers.flash_attention`` (forward, recompute
    and fused backward) in a checkpointed layer scan, as the train step
    runs it; every kernel call maps to the ``attention`` scope once
    ``scripts/scope_split.py`` joins its multi-line HLO instructions."""
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "scope_split", root / "scripts" / "scope_split.py")
    scope_split = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scope_split)
    from bench import scopes

    def loss(q, k, v):
        def body(x, _):
            return (x + layers.flash_attention(x, k, v)).astype(x.dtype), None
        body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("backbone"):
            out, _ = jax.lax.scan(body, q, None, length=2)
        return jnp.sum(out.astype(jnp.float32))

    bf16 = jnp.bfloat16
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        _sds(one_chip, (1, SEQ, HEADS, HEAD_DIM), bf16),
                        _sds(one_chip, (1, SEQ, KV_HEADS, HEAD_DIM), bf16),
                        _sds(one_chip, (1, SEQ, KV_HEADS, HEAD_DIM), bf16))
    text = scope_split.one_line_instructions(compiled.as_text())
    kernels = scope_split.kernel_names(text)
    paths = scopes.op_paths(text)
    assert len(kernels) >= 3  # forward, its recompute, fused backward
    assert {scopes.scope_of(paths[n]) for n in kernels} == {"attention"}
    assert any(scopes.REMAT_MARK in paths[n].split("/") for n in kernels)
