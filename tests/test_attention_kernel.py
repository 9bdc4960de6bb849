"""Causal self-attention on the splash flash kernels (`layers.flash_attention`)
against the jnp chunk loop (`layers.chunked_attention`), the path each input
takes (`layers.flash_applies`), and the layer scope the kernels' ops land in.

Off a TPU the kernels run in interpret mode.  Tests that need the TPU branch
of the dispatch patch `layers._on_tpu`.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_config
from repro.models import layers as L
from repro.models import transformer
from repro.sharding.rules import ShardCtx, local_ctx

ROOT = pathlib.Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "scope_split", ROOT / "scripts" / "scope_split.py")
scope_split = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(scope_split)
from bench import scopes  # noqa: E402  (scope_split puts the root on the path)

B, S, HD = 2, 256, 128
#: the largest gap allowed, as a share of the reference's largest entry: a
#: few units in the last place of bfloat16 (eps 2**-8), since the kernel
#: rounds q / sqrt(hd) and its probabilities to bf16 where the loop keeps f32
BF16_TOL = 3 * 2.0 ** -8


def _qkv(h, kv, s=S, hd=HD, hv=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, s, h, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, s, kv, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, s, kv, hv or hd)).astype(jnp.bfloat16)
    return q, k, v


def _chunked(q, k, v):
    return L.chunked_attention(q, k, v, causal=True, chunk=128)


def _assert_close(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape
    gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert gap <= BF16_TOL, gap


def _grads(attn, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("h,kv", [(4, 2), (4, 4)], ids=["gqa", "mha"])
def test_kernel_matches_chunk_loop_forward_and_grads(h, kv):
    q, k, v = _qkv(h, kv)
    out = jax.jit(L.flash_attention)(q, k, v)
    assert out.dtype == q.dtype
    _assert_close(out, jax.jit(_chunked)(q, k, v))
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    for got, want in zip(_grads(L.flash_attention, q, k, v, w),
                         _grads(_chunked, q, k, v, w)):
        _assert_close(got, want)


def _layers_under_remat(attn):
    """Two layers of attention in a checkpointed scan, as the model runs
    them: each layer's output feeds the next layer's queries."""
    def f(q, k, v, w):
        def body(x, _):
            return (x + attn(x, k, v)).astype(x.dtype), None
        body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("backbone"):
            out, _ = jax.lax.scan(body, q, None, length=2)
        return jnp.sum(out.astype(jnp.float32) * w)
    return f


def test_kernel_under_checkpoint_in_scan():
    q, k, v = _qkv(4, 2)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    got = jax.jit(jax.grad(_layers_under_remat(L.flash_attention),
                           argnums=(0, 1, 2)))(q, k, v, w)
    want = jax.jit(jax.grad(_layers_under_remat(_chunked),
                            argnums=(0, 1, 2)))(q, k, v, w)
    for a, b in zip(got, want):
        _assert_close(a, b)


# --- dispatch ----------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(L, "_on_tpu", lambda: True)


def _applies(q, k, v, causal=True, ctx=None):
    return L.flash_applies(q, k, v, causal=causal, ctx=ctx or local_ctx())


def test_cell_shape_takes_the_kernel_on_tpu(on_tpu):
    assert _applies(*_qkv(24, 2, s=2048))
    assert _applies(*_qkv(4, 4))
    one = ShardCtx(mesh=AbstractMesh((1, 1), ("data", "model")))
    assert _applies(*_qkv(4, 2), ctx=one)


@pytest.mark.parametrize("case", ["non_causal", "hv_ne_hd", "hd64", "s200",
                                  "sq_ne_sk", "mesh4"])
def test_other_inputs_keep_the_chunk_loop_on_tpu(on_tpu, case):
    q, k, v = _qkv(4, 2)
    kw = {}
    if case == "non_causal":
        kw["causal"] = False
    elif case == "hv_ne_hd":
        q, k, v = _qkv(4, 2, hv=256)
    elif case == "hd64":
        q, k, v = _qkv(4, 2, hd=64)
    elif case == "s200":
        q, k, v = _qkv(4, 2, s=200)
    elif case == "sq_ne_sk":
        k, v = _qkv(4, 2, s=2 * S)[1:]
    else:
        kw["ctx"] = ShardCtx(mesh=AbstractMesh((2, 2), ("data", "model")))
    assert not _applies(q, k, v, **kw)


def test_cpu_backend_keeps_the_chunk_loop():
    assert jax.default_backend() != "tpu"
    assert not _applies(*_qkv(24, 2, s=2048))


def _attn_cfg(**kw):
    return get_config("starcoder2-3b").reduced(
        head_dim=HD, n_layers=2, dtype="bfloat16", param_dtype="bfloat16",
        remat=True, **kw)


@pytest.fixture
def path_spy(monkeypatch):
    """Counts the calls of each attention path, keeping what they do."""
    calls = {"flash": 0, "chunked": 0}

    def spy(name, fn):
        def wrapped(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapped
    monkeypatch.setattr(L, "flash_attention",
                        spy("flash", L.flash_attention))
    monkeypatch.setattr(L, "chunked_attention",
                        spy("chunked", L.chunked_attention))
    return calls


def _attn_params(cfg):
    return L.init_attention(jax.random.PRNGKey(1), cfg)


def test_kv_override_keeps_the_chunk_loop(on_tpu, path_spy):
    cfg = _attn_cfg()
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model)
                          ).astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    p = _attn_params(cfg)
    kv = L.cross_kv(p, x, cfg, local_ctx())
    L.attn_forward(p, x, pos, cfg, local_ctx(), kv_override=kv)
    assert path_spy == {"flash": 0, "chunked": 1}
    L.attn_forward(p, x, pos, cfg, local_ctx())
    assert path_spy == {"flash": 1, "chunked": 1}


def _parent_attn_forward(p, x, positions, cfg, ctx, *, causal=True,
                         kv_override=None):
    """`attn_forward` as it was before the kernel path."""
    q, k, v = L._qkv(p, x, cfg, positions, ctx, rope_on=not cfg.learned_pos)
    if kv_override is not None:
        k, v = kv_override
    out = L.chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    out = ctx.act(out, "bsh.")
    y = out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"].astype(x.dtype)
    return ctx.act(y, "bO.")


def _hidden(cfg, tokens):
    params = transformer.init_lm(jax.random.PRNGKey(0), cfg, local_ctx())
    fwd = jax.jit(lambda p, t: transformer.hidden_states(p, t, cfg,
                                                         local_ctx())[0])
    return fwd(params, tokens)


def _tokens(cfg, s=S):
    return jax.random.randint(jax.random.PRNGKey(3), (B, s), 0,
                              cfg.vocab_size)


def test_cpu_model_forward_is_the_parents_bit_for_bit(monkeypatch, path_spy):
    cfg = _attn_cfg()
    tokens = _tokens(cfg)
    got = _hidden(cfg, tokens)
    assert path_spy["flash"] == 0 and path_spy["chunked"] > 0
    monkeypatch.setattr(L, "attn_forward", _parent_attn_forward)
    want = _hidden(cfg, tokens)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_model_forward_on_the_kernel_matches_the_chunk_loop(monkeypatch,
                                                            path_spy):
    cfg = _attn_cfg()
    tokens = _tokens(cfg)
    want = _hidden(cfg, tokens)
    monkeypatch.setattr(L, "_on_tpu", lambda: True)
    got = _hidden(cfg, tokens)
    assert path_spy["flash"] > 0
    # two layers of bf16 residual stream and norms: a bf16 ulp of each
    # layer's output apart at most, besides the attention's own rounding
    _assert_close(got, want)


# --- layer scope ---------------------------------------------------------------


def test_kernel_ops_land_in_the_attention_scope():
    """Every instruction that the kernel path adds to a checkpointed grad,
    forward, recompute and backward, maps to ``attention``."""
    q, k, v = _qkv(4, 2)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    grad = jax.jit(jax.grad(_layers_under_remat(L.flash_attention),
                            argnums=(0, 1, 2)))
    text = grad.lower(q, k, v, w).compile().as_text()
    paths = scopes.op_paths(scope_split.one_line_instructions(text))
    kernel = {n: p for n, p in paths.items() if "_splash_attention" in p}
    assert kernel
    assert {scopes.scope_of(p) for p in kernel.values()} == {"attention"}
    assert any("transpose(" in p for p in kernel.values())
    assert any(scopes.REMAT_MARK in p.split("/") for p in kernel.values())


# --- scripts/scope_split.py's reading of kernel calls --------------------------

HLO = """\
HloModule jit_train_step, is_scheduled=true

ENTRY %main.9 (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %splash_mqa_fwd.1 = (bf16[8]{0}, f32[8]{0}) custom-call(%x), \
custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"use_fused_bwd_kernel\\": true}"
}}, metadata={op_name="jit(f)/backbone/attention/vmap(jit(_splash_attention))\
/splash_mqa_fwd/pallas_call" stack_frame_id=3}, backend_config={"a":{"b":1}}
  %fusion.2 = bf16[8]{0} fusion(%splash_mqa_fwd.1), kind=kLoop, \
metadata={op_name="jit(f)/backbone/attention/transpose"}
  ROOT %fusion.3 = bf16[8]{0} fusion(%fusion.2), kind=kLoop, \
metadata={op_name="jit(f)/backbone/mul"}
}
"""


def test_one_line_instructions_finds_a_kernels_scope():
    assert scopes.scope_of(scopes.op_paths(HLO)["splash_mqa_fwd.1"]) \
        == "unscoped"
    text = scope_split.one_line_instructions(HLO)
    paths = scopes.op_paths(text)
    assert scopes.scope_of(paths["splash_mqa_fwd.1"]) == "attention"
    assert scopes.scope_of(paths["fusion.2"]) == "attention"
    assert scopes.scope_of(paths["fusion.3"]) == "backbone"
    assert len(text.splitlines()) == len(HLO.splitlines()) - 2
    assert scope_split.kernel_names(text) == {"splash_mqa_fwd.1"}


def test_kernel_share_of_a_scope():
    from bench.tracing import Trace
    run = {"hlo_text": scope_split.one_line_instructions(HLO),
           "lo": 0, "hi": 100,
           "trace": Trace(devices={"/device:TPU:0": [
               (0, 30, "splash_mqa_fwd.1"), (30, 40, "fusion.2"),
               (40, 90, "fusion.3"), (90, 200, "splash_mqa_fwd.1")]},
               spans=[])}
    # attention: 30 + 10 (window cut at 100) of kernel, 10 of transpose
    assert scope_split.kernel_share(run, "attention") == pytest.approx(0.8)
    assert scope_split.kernel_share(run, "backbone") == 0.0
    assert scope_split.kernel_share(run, "optimizer") is None
