"""Plain references of what the timed paths compute, in float32.

Written from the models' descriptions in straightforward ``jax.numpy``;
nothing here imports the program under test or takes anything it made.
The inputs (weights, the sampler's projection, batches, step keys) come
from the benchmark's own seeded generators (``weights.py``, the family's
``ring``), the same ones that fed the program.

* the backbone: ``hidden`` of the configuration's family
  (``bench/families/<family>.py``) -> (h (T, d), labels (T,)).
* ``draw``: the two-level quadratic-kernel sampler (paper §3.2 in its
  two-level form: one block by block mass, then one class inside it by
  exact kernel score), chosen by the configuration's ``sampler``:
  ``block-quadratic-shared`` draws one set of m negatives for the batch
  from the batch-summed kernel; ``block-quadratic`` draws m i.i.d.
  negatives per example.  It follows the draw protocol of the kernel
  sampler (which key draws the block, which the class, Gumbel-max or
  inverse-CDF) so that its draws are the program's own up to
  floating-point ties, and reports the EXACT log q of each draw from
  per-class scores over the whole catalog.
* ``sampled_loss``: eq. 2-3 (corrected negatives, accidental hits
  masked), over |o| where the configuration has ``abs_softmax`` (eq. 11).
* ``train_steps``: two steps of global-norm clipping (1.0) + AdamW from
  the seed, returning each step's loss, the first clipped gradient's norm
  per leaf and the parameters' change after the two, per leaf.  Two steps
  and not three: a third needs AdamW's two float32 moments beside the
  float32 parameters and gradient, which with the gradient program does
  not fit one chip at the cell's size.

Matmuls run at ``precision=HIGHEST``.  ``mode="fp8"`` is the control, the
usual float8 training recipe: each matmul operand is rounded to float8 e4m3
with a per-tensor scale, and each cotangent that enters a backward matmul
to float8 e5m2, so the backward matmuls see the rounded operands and the
rounded cotangents; sums, norms and the softmax stay in float32.
``mode="bf16"`` is a witness that computes as the configuration states:
matmul operands in bfloat16, and for a model whose ``dtype`` is bfloat16
results and stored activations too (a float32 model on the TPU's default
precision rounds a matmul's operands and keeps its result in float32).
Parameters are rounded to their stored type after each update.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


# --- arithmetic at a stated precision ----------------------------------------


def _scaled_round(x, dtype):
    """x rounded to the float8 ``dtype`` under a per-tensor scale (its
    largest magnitude maps to the type's largest finite value), as
    float32."""
    x = x.astype(jnp.float32)
    s = F8_MAX[dtype] / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def round_fp8(x):
    """A matmul operand in float8 e4m3; its cotangent passes as it is."""
    return _scaled_round(x, jnp.float8_e4m3fn)


round_fp8.defvjp(lambda x: (round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def round_grad_fp8(y):
    """A matmul's result as it is; its cotangent, the operand of the
    backward matmuls, in float8 e5m2."""
    return y


round_grad_fp8.defvjp(lambda y: (y, None),
                      lambda _, g: (_scaled_round(g, jnp.float8_e5m2),))


def round_to(dtype, x):
    """x (float32) rounded to the float ``dtype``'s precision, kept as
    float32.  A ``reduce_precision`` and not a round trip through the
    type: under XLA's default excess precision a round trip whose result
    is read back as float32 may be left out of the compiled program."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _round_bf16(x):
    """x rounded to bfloat16, as float32."""
    return round_to(jnp.bfloat16, x.astype(jnp.float32))


def rounder(mode: str):
    """x -> x as the mode's matmul would see it (float32 values)."""
    if mode == "fp32":
        return lambda x: x.astype(jnp.float32)
    if mode == "fp8":
        return round_fp8
    if mode == "bf16":
        return _round_bf16
    raise ValueError(f"unknown reference mode {mode!r}")


def stored(mode: str, x):
    """An activation as the mode holds it between operations."""
    if mode == "fp8":
        return round_fp8(x)
    return _round_bf16(x) if mode == "bf16" else x


def einsum(mode: str, spec: str, *xs, store: bool = True):
    """A matmul as the mode computes it.  ``store=False`` keeps a bf16
    mode's result in float32, as a float32 model computes (``stores``)."""
    r = rounder(mode)
    out = jnp.einsum(spec, *(r(x) for x in xs), precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    if mode == "fp8":
        return stored(mode, round_grad_fp8(out))
    return stored(mode, out) if store else out


def stores(cfg: dict) -> bool:
    """Whether the model holds its activations in a type below float32."""
    return cfg.get("dtype") != "float32"


# --- the sampler ------------------------------------------------------------


def _inverse_cdf_rows(key, logits, m: int):
    """m draws per row of logits (R, P) by one uniform each."""
    cdf = jnp.cumsum(jax.nn.softmax(logits, axis=-1), axis=-1)
    u = jax.random.uniform(key, (logits.shape[0], m), dtype=cdf.dtype)
    idx = jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="right"))(cdf, u)
    return jnp.minimum(idx, logits.shape[-1] - 1)


def _log_scores(scores):
    return jnp.where(scores > 0, jnp.log(jnp.maximum(scores, 1e-30)),
                     -jnp.inf)


def _two_level(scores, n_valid: int, block: int):
    """Per-class kernel scores (..., n) -> (padded log scores (..., nb,
    block), log block masses (..., nb), exact per-class log q (..., n))."""
    n = scores.shape[-1]
    nb = -(-n // block)
    pad = nb * block - n
    valid = jnp.arange(n) < n_valid
    scores = jnp.where(valid, scores, 0.0)
    logq = _log_scores(scores) - jnp.log(jnp.sum(scores, -1, keepdims=True))
    sp = jnp.pad(scores, [(0, 0)] * (scores.ndim - 1) + [(0, pad)])
    sp = sp.reshape(*scores.shape[:-1], nb, block)
    mass = jnp.sum(sp, axis=-1)
    return _log_scores(sp), jnp.log(jnp.maximum(mass, 1e-30)), logq


def draw(h, w, key, cfg: dict, proj=None):
    """Negatives and their exact log q: ids, logq (m,) shared over the
    batch, or (T, m) per example, by ``cfg["sampler"]``.
    h: (T, d), w: (n, d)."""
    hq = h if proj is None else jnp.einsum("td,rd->tr", h, proj,
                                           precision=HIGHEST)
    wq = w if proj is None else jnp.einsum("nd,rd->nr", w, proj,
                                           precision=HIGHEST)
    if cfg["sampler"] == "block-quadratic-shared":
        return _draw_shared(hq, wq, key, cfg)
    if cfg["sampler"] == "block-quadratic":
        return _draw_per_example(hq, wq, key, cfg)
    raise ValueError(f"no reference for sampler {cfg['sampler']!r}")


def _draw_shared(hq, wq, key, cfg: dict):
    """m negatives shared over the batch, from the batch-summed kernel."""
    alpha, m = cfg["sampler_alpha"], cfg["m_negatives"]
    block, n = cfg["sampler_block"], cfg["vocab_size"]
    t = hq.shape[0]
    hh = jnp.einsum("ti,tj->ij", hq, hq, precision=HIGHEST)
    quad = jnp.einsum("nr,rs,ns->n", wq, hh, wq, precision=HIGHEST)
    log_in, log_blk, logq = _two_level(alpha * quad + t, n, block)
    k_blk, k_in = jax.random.split(key)
    blk = jax.random.categorical(k_blk, log_blk, shape=(m,))
    if m >= 4 * log_blk.shape[0]:
        within = _inverse_cdf_rows(k_in, log_in[blk], 1)[:, 0]
    else:
        within = jax.random.categorical(k_in, log_in[blk], axis=-1)
    ids = (blk * block + within).astype(jnp.int32)
    return ids, logq[ids]


#: examples whose per-class scores over the whole catalog are held at once
#: by the per-example draw ((rows, n) float32 each)
DRAW_ROWS = 16


def _draw_per_example(hq, wq, key, cfg: dict):
    """m i.i.d. negatives per example: the batch's key split over the
    examples, each example's split into the block's key and the class's;
    the exact per-class log q over all n classes, DRAW_ROWS examples at a
    time."""
    alpha, m = cfg["sampler_alpha"], cfg["m_negatives"]
    block, n = cfg["sampler_block"], cfg["vocab_size"]

    def one(args):
        hq1, k = args
        dots = jnp.einsum("nr,r->n", wq, hq1, precision=HIGHEST)
        log_in, log_blk, logq = _two_level(alpha * jnp.square(dots) + 1.0,
                                           n, block)
        k_blk, k_in = jax.random.split(k)
        blk = jax.random.categorical(k_blk, log_blk, shape=(m,))
        within = jax.random.categorical(k_in, log_in[blk], axis=-1)
        ids = (blk * block + within).astype(jnp.int32)
        return ids, logq[ids]

    keys = jax.random.split(key, hq.shape[0])
    return jax.lax.map(one, (hq, keys), batch_size=DRAW_ROWS)


def sampled_loss(h, w, labels, ids, logq, cfg: dict, mode: str = "fp32"):
    """Per-target eq. 3 loss over [positive, m corrected negatives]; ids
    and logq (m,) shared over the batch or (T, m) per example.  With
    ``abs_softmax`` the logits are |o| before the correction (eq. 11)."""
    m, store = cfg["m_negatives"], stores(cfg)
    pos = einsum(mode, "td,td->t", h, w[labels], store=store)
    if ids.ndim == 1:
        neg = einsum(mode, "td,md->tm", h, w[ids], store=store)
        logq, hit = logq[None, :], ids[None, :] == labels[:, None]
    else:
        neg = einsum(mode, "td,tmd->tm", h, w[ids], store=store)
        hit = ids == labels[:, None]
    if cfg.get("abs_softmax"):
        pos, neg = jnp.abs(pos), jnp.abs(neg)
    neg = jnp.where(hit, -jnp.inf, neg - (logq + math.log(m)))
    allv = jnp.concatenate([pos[:, None], neg], axis=-1)
    return jax.nn.logsumexp(allv, axis=-1) - pos


# --- two train steps --------------------------------------------------------


def make_loss(cfg: dict, mode: str, half: bool):
    family = families.load(cfg)

    def loss(p, batch, key, proj):
        h, labels = family.hidden(p, batch, cfg, mode)
        if half:  # fault: half of the batch left out, mean over the rest
            h, labels = h[: h.shape[0] // 2], labels[: h.shape[0] // 2]
        w = family.head_table(p).astype(jnp.float32)
        ids, logq = draw(jax.lax.stop_gradient(h), jax.lax.stop_gradient(w),
                         key, cfg, proj)
        return jnp.mean(sampled_loss(h, w, labels, jax.lax.stop_gradient(ids),
                                     jax.lax.stop_gradient(logq), cfg, mode))
    return loss


def loss_at(params, batch, key, cfg: dict, proj=None) -> float:
    """The float32 loss of one batch at the given parameters (any float
    dtype): a forward pass only."""
    loss = jax.jit(lambda p, b, k, pr: make_loss(cfg, "fp32", False)(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p), b, k,
        pr))
    return float(loss(params, batch, key, proj))


def losses_under_keys(params, batch, keys, cfg: dict, proj=None) -> list:
    """The float32 loss of one batch at the given parameters under each of
    ``keys`` (each draws its own negatives): a forward pass of the
    backbone, and one draw and head per key."""
    family = families.load(cfg)

    def run(p, b, ks, pr):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        h, labels = family.hidden(p, b, cfg)
        w = family.head_table(p).astype(jnp.float32)

        def one(k):
            ids, logq = draw(h, w, k, cfg, pr)
            return jnp.mean(sampled_loss(h, w, labels, ids, logq, cfg))
        return jax.lax.map(one, ks)
    return [float(x) for x in jax.jit(run)(params, batch, jnp.stack(keys),
                                           proj)]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def train_steps(params0, batches, keys, cfg: dict, opt: dict, *,
                proj=None, mode: str = "fp32", half: bool = False,
                remake_params0=None, keep_grad1: bool = False,
                against=None) -> dict:
    """Two clipped-AdamW steps from ``params0``.

    params0 is released once copied to float32; ``remake_params0()``
    makes it again for the change norms once the gradients are freed.  Returns
    {"loss": [l1, l2], "grad_norm": (leaves,), "change_norm": (leaves,)};
    with ``keep_grad1`` also "grad1", the first clipped gradient's leaves
    on the host; with ``against`` ({name: such host leaves}) also
    "diff_norm": {name: (leaves,) norms of their difference from this
    run's first clipped gradient}.
    """
    b1, b2 = opt["b1"], opt["b2"]
    eps, wd, lr = opt["eps"], opt["weight_decay"], opt["lr"]
    store = jax.tree_util.tree_map(lambda a: a.dtype, params0)
    tmap = jax.tree_util.tree_map

    vg = jax.jit(jax.value_and_grad(make_loss(cfg, mode, half)))

    def clip(g):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(norm, 1e-9))
        return tmap(lambda x: x * scale, g)

    def apply(p, upd):
        # as stored: the update in the parameter's type, then the sum
        return tmap(lambda x, u, dt: round_to(dt, x + round_to(dt, u)),
                    p, upd, store)

    def adam_upd(m, v, p, step):
        c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
        return tmap(lambda m_, v_, p_: -lr * ((m_ / c1)
                                              / (jnp.sqrt(v_ / c2) + eps)
                                              + wd * p_), m, v, p)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step1(p, g):
        g = clip(g)
        m = tmap(lambda g_: (1 - b1) * g_, g)
        v = tmap(lambda g_: (1 - b2) * jnp.square(g_), g)
        return apply(p, adam_upd(m, v, p, 1.0)), g, leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step2(p, g1, g2):
        g2 = clip(g2)
        m = tmap(lambda a, b: b1 * ((1 - b1) * a) + (1 - b1) * b, g1, g2)
        v = tmap(lambda a, b: b2 * ((1 - b2) * jnp.square(a))
                 + (1 - b2) * jnp.square(b), g1, g2)
        return apply(p, adam_upd(m, v, p, 2.0))

    p = jax.jit(lambda t: tmap(lambda a: a.astype(jnp.float32), t))(params0)
    del params0
    l1, g = vg(p, batches[0], keys[0], proj)
    p, g1, gnorm = step1(p, g)
    del g
    out = {}
    if keep_grad1:
        out["grad1"] = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            jax.device_get(g1))]
    if against:
        mine = jax.tree_util.tree_leaves(g1)
        out["diff_norm"] = {
            name: np.array([float(_diff_norm(a, b))
                            for a, b in zip(leaves, mine)])
            for name, leaves in against.items()}
        table = next(i for i, x in enumerate(mine)
                     if x is families.load(cfg).head_table(g1))
        moved = np.asarray(moved_rows(mine[table]))
        out["rows_gap"] = {name: rows_gap(moved_rows(leaves[table]), moved)
                           for name, leaves in against.items()}
    l2, g = vg(p, batches[1], keys[1], proj)
    p = step2(p, g1, g)
    del g, g1
    p0 = remake_params0()
    change = jax.jit(lambda a, b: leaf_norms(
        tmap(lambda x, y: x - y.astype(jnp.float32), a, b)))(p, p0)
    out.update(loss=[float(l1), float(l2)], grad_norm=np.asarray(gnorm),
               change_norm=np.asarray(change))
    return out


@jax.jit
def moved_rows(table_grad):
    """(n,) whether each class row's gradient is not nought."""
    return jnp.any(table_grad != 0, axis=-1)


def rows_gap(got, want) -> float:
    """Share of the class rows that move on one side and not on the other,
    over the rows that ``want`` moves."""
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    return float(np.sum(got != want) / max(1, np.sum(want)))


@jax.jit
def _diff_norm(host_leaf, leaf):
    """||host_leaf - leaf||, one leaf on the device at a time."""
    return jnp.sqrt(jnp.sum(jnp.square(host_leaf.astype(jnp.float32)
                                       - leaf)))
