"""Jit'd public wrappers for the Pallas kernels.

Handles padding to tile multiples, dtype policy, GQA head expansion, and
backend dispatch: on TPU the kernels run compiled; elsewhere they run in
interpret mode (the kernel body executes op-by-op on CPU — correctness
validation only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.block_scores import block_scores as _block_scores
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_head import MASK_CORR
from repro.kernels.fused_head import fused_lse as _fused_lse
from repro.kernels.fused_head import fused_lse_bwd as _fused_lse_bwd
from repro.kernels.leaf_scores import leaf_scores as _leaf_scores
from repro.kernels.midx_scores import midx_pair_masses as _midx_pair
from repro.kernels import ref
from repro.kernels.rff_features import rff_features as _rff_features
from repro.kernels.sampled_loss import sampled_loss as _sampled_loss
from repro.kernels.zstats import zstats as _zstats

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


#: VMEM bytes one pipelined tile may take.  Pallas double-buffers every
#: block and the kernel bodies hold fp32 temporaries of about a tile, so
#: this keeps each kernel well inside the 16 MiB default scoped VMEM.
TILE_VMEM_BYTES = 2 * 1024 * 1024


def _pad_to(x: Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _sublane_tile(n: int, cap: int = 128) -> int:
    """Power-of-two tile of at least 8 rows for a second-minor block dim."""
    return min(cap, max(8, 1 << (n - 1).bit_length()))


def _divisor_tile(n: int, unit_bytes: int, align: int = 128) -> int:
    """Tile of an axis of length n whose every index costs ``unit_bytes`` of
    VMEM: the whole axis when it fits ``TILE_VMEM_BYTES``, else the largest
    multiple of ``align`` that divides n and fits (n itself if none does)."""
    if n * unit_bytes <= TILE_VMEM_BYTES:
        return n
    t = TILE_VMEM_BYTES // unit_bytes // align * align
    while t >= align:
        if n % t == 0:
            return t
        t -= align
    return n


def zstats(w: Array) -> Array:
    """w: (n_blocks, B, r) -> (n_blocks, r, r) fp32 block Grams."""
    return _zstats(w, interpret=_interpret())


def block_scores(h: Array, z: Array, cnt: Array,
                 alpha: float = 100.0) -> Array:
    """h: (T, r); z: (N, r, r); cnt: (N,) -> (T, N) kernel masses."""
    r = z.shape[-1]
    t_tile = _sublane_tile(h.shape[0])
    # (Nt, r, r) stats tile; Nt is the output's lane dim: all of N, or a
    # multiple of 128.
    n_tile = z.shape[0]
    if n_tile * r * r * 4 > TILE_VMEM_BYTES:
        n_tile = max(128, TILE_VMEM_BYTES // (r * r * 4) // 128 * 128)
    hp, t = _pad_to(h, 0, t_tile)
    zp, n = _pad_to(z, 0, n_tile)
    cp, _ = _pad_to(cnt, 0, n_tile)
    out = _block_scores(hp, zp, cp, alpha=alpha,
                        t_tile=min(t_tile, hp.shape[0]),
                        n_tile=n_tile, interpret=_interpret())
    return out[:t, :n]


def _leaf_call(h: Array, table: Array, idx: Array, *, alpha: float,
               square: bool) -> Array:
    _, b, r = table.shape
    # per leaf row: its fp32 tile row plus one column of the resident
    # (m, Bt) output block
    b_tile = _divisor_tile(b, 4 * (r + idx.shape[1]))
    return _leaf_scores(h, table, idx, alpha=alpha, square=square,
                        b_tile=b_tile, interpret=_interpret())


def leaf_scores(h: Array, table: Array, idx: Array,
                alpha: float = 100.0) -> Array:
    """h: (T, r); table: (L, B, r); idx: (T, m) leaf ids -> (T, m, B)
    quadratic-kernel scores of every row of each query's drawn leaves."""
    return _leaf_call(h, table, idx, alpha=alpha, square=True)


def leaf_dots(h: Array, table: Array, idx: Array) -> Array:
    """h: (T, r); table: (L, B, r); idx: (T, m) -> (T, m, B) raw dots
    <h_t, w_{idx[t, j], b}>.

    The exact-scoring step of serving-side beam retrieval: same kernel and
    VMEM schedule as ``leaf_scores``, without the kernelization."""
    return _leaf_call(h, table, idx, alpha=0.0, square=False)


def midx_list_masses(h: Array, c1: Array, c2: Array, codes: Array,
                     cnt: Array, alpha: float = 100.0) -> Array:
    """h: (T, d); c1: (K1, d); c2: (K2, d); codes: (P, 2); cnt: (P,)
    -> (T, P) fp32 stage-1 MIDX sampling masses (DESIGN.md §2.9).

    The codeword-PAIR expansion ct[j] = c1[a1_j] + c2[a2_j] is an O(P d)
    XLA gather here; the kernel fuses the matvec + kernel transform +
    count multiply.  Padded lists get cnt 0, hence mass exactly 0."""
    ct = (c1.astype(jnp.float32)[codes[:, 0]]
          + c2.astype(jnp.float32)[codes[:, 1]])
    t_tile = _sublane_tile(h.shape[0])
    p_tile = min(128, max(8, 1 << (ct.shape[0] - 1).bit_length()))
    hp, t = _pad_to(h, 0, t_tile)
    ctp, p = _pad_to(ct, 0, p_tile)
    cp, _ = _pad_to(cnt, 0, p_tile)
    out = _midx_pair(hp, ctp, cp, alpha=alpha,
                     t_tile=min(t_tile, hp.shape[0]),
                     p_tile=min(p_tile, ctp.shape[0]),
                     interpret=_interpret())
    return out[:t, :p]


def rff_features(w: Array, omega: Array, mask: Array, logshift: Array, *,
                 tau: float = 1.0) -> Array:
    """w: (L, B, d); omega: (D, d); mask: (L, B); logshift: () traced scalar
    -> (L, D) fp32 masked per-leaf positive-RFF feature sums.

    Fuses phi(w) with the per-leaf reduction (DESIGN.md §2.7) — the (n, D)
    feature matrix never hits HBM.  Padded feature columns (zero omega rows)
    produce junk that is sliced off; padded leaf rows are masked to zero."""
    n_feat = omega.shape[0]
    d = w.shape[-1]
    d_tile = min(128, max(8, 1 << (n_feat - 1).bit_length()))
    # leaf rows are the second-minor dim of the flat (L*B, d) class view
    wp, _ = _pad_to(w, 1, 8)
    mp, _ = _pad_to(mask, 1, 8)
    op, _ = _pad_to(omega, 0, d_tile)
    # per class row: its fp32 tile row, the w*w temporary, one feature row
    b_tile = _divisor_tile(wp.shape[1], 4 * (2 * d + d_tile), align=8)
    out = _rff_features(wp, op, mp, jnp.reshape(logshift, (1, 1)),
                        tau=tau, d_total=n_feat, b_tile=b_tile,
                        d_tile=min(d_tile, op.shape[0]),
                        interpret=_interpret())
    return out[:, :n_feat]


def sampled_loss(h: Array, w_neg: Array, logq: Array, pos_logit: Array,
                 m_total: int | None = None) -> Array:
    """Fused corrected sampled-softmax loss, shared negatives.  -> (T,)."""
    m = w_neg.shape[0]
    m_total = m_total or m
    t_tile = min(128, max(8, 1 << (h.shape[0] - 1).bit_length()))
    m_tile = min(128, max(8, 1 << (m - 1).bit_length()))
    hp, t = _pad_to(h, 0, t_tile)
    pp, _ = _pad_to(pos_logit, 0, t_tile)
    wp, _ = _pad_to(w_neg, 0, m_tile)
    # padded negatives must contribute zero mass: logq = +inf-ish correction
    lp = jnp.pad(logq, (0, wp.shape[0] - m), constant_values=1e30)
    out = _sampled_loss(hp, wp, lp, pp, m_total=m_total,
                        t_tile=min(t_tile, hp.shape[0]),
                        m_tile=min(m_tile, wp.shape[0]),
                        interpret=_interpret())
    return out[:t]


# --- fused sampled-softmax head (kernels/fused_head.py) ----------------------

#: token-chunk size of the non-TPU fallback: peak gather is (chunk, K, d).
FUSED_HEAD_CHUNK = 128
#: VMEM budget for the Pallas backward's resident (n, d) dL/dw accumulator;
#: larger head shards take the chunked path.
FUSED_HEAD_VMEM_BYTES = 8 * 1024 * 1024


def resolve_fused_impl(impl: str, n: int, d: int) -> str:
    """The path ``fused_head_lse`` takes for an (n, d) head table.

    "auto" is the Pallas kernel on TPU when the (n, d) fp32 dL/dw
    accumulator fits ``FUSED_HEAD_VMEM_BYTES``, else the chunked jnp path.
    An explicit "pallas" whose accumulator does not fit is refused here:
    the TPU compiler would refuse it anyway."""
    if impl not in ("auto", "pallas", "chunked"):
        raise ValueError(f"fused_head_lse impl={impl!r} not in "
                         "('auto', 'pallas', 'chunked')")
    fits = n * d * 4 <= FUSED_HEAD_VMEM_BYTES
    if impl == "pallas" and not fits:
        raise ValueError(
            f"fused_head_lse impl='pallas' needs the ({n}, {d}) fp32 dL/dw "
            f"accumulator in VMEM ({n * d * 4} bytes > "
            f"FUSED_HEAD_VMEM_BYTES={FUSED_HEAD_VMEM_BYTES}); use 'auto' "
            "or 'chunked'")
    if impl != "auto":
        return impl
    return "pallas" if fits and not _interpret() else "chunked"


def _fused_chunks(t: int, *arrays):
    """Pad the token axis to a FUSED_HEAD_CHUNK multiple and stack chunks."""
    tc = min(FUSED_HEAD_CHUNK, t)
    pad = (-t) % tc
    out = []
    for a, fill in arrays:
        ap = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                     constant_values=fill)
        out.append(ap.reshape(-1, tc, *a.shape[1:]))
    return out


def _chunked_lse(w, h, ids, corr, biasg, abs_mode):
    """Non-TPU forward: lax.map over token chunks — peak intermediate is a
    (chunk, K, d) gather instead of (T, K, d).  Each chunk IS the dense
    oracle (ref.fused_lse_ref gathers rows before upcasting, so no fp32
    copy of the whole table is ever made)."""
    t = h.shape[0]

    def one(args):
        h_c, ids_c, corr_c, bias_c = args
        return ref.fused_lse_ref(w, h_c, ids_c, corr_c, bias_c, abs_mode)

    xs = _fused_chunks(t, (h, 0), (ids, 0), (corr, MASK_CORR), (biasg, 0))
    return jax.lax.map(one, tuple(xs)).reshape(-1)[:t]


def _chunked_lse_bwd(w, h, ids, corr, biasg, lse, gbar, abs_mode):
    """Non-TPU backward: scan over token chunks carrying the (n, d) dL/dw
    accumulator; recomputes the forward per chunk (flash-style)."""
    n, d = w.shape
    t = h.shape[0]

    def body(dw, args):
        h_c, ids_c, corr_c, bias_c, lse_c, g_c = args
        h32 = h_c.astype(jnp.float32)
        rows = w[ids_c].astype(jnp.float32)  # gather, THEN upcast (tc, K, d)
        o = jnp.einsum("tkd,td->tk", rows, h32) + bias_c
        tl = jnp.abs(o) if abs_mode else o
        p = jnp.exp((tl - corr_c) - lse_c[:, None]) * g_c[:, None]
        dcorr_c = -p  # corr applies after |.|: no sign chain
        if abs_mode:
            p = p * jnp.sign(o)
        dh_c = jnp.einsum("tk,tkd->td", p, rows)
        dw = dw.at[ids_c].add(p[..., None] * h32[:, None, :])
        return dw, (dh_c, p, dcorr_c)

    xs = _fused_chunks(t, (h, 0), (ids, 0), (corr, MASK_CORR), (biasg, 0),
                       (lse, 0), (gbar, 0))
    dw, (dh, dcoef, dcorr) = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32),
                                          tuple(xs))
    k = ids.shape[1]
    return (dw, dh.reshape(-1, d)[:t], dcoef.reshape(-1, k)[:t],
            dcorr.reshape(-1, k)[:t])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_head_lse(w, h, ids, corr, biasg, abs_mode, impl):
    return _fused_head_lse_fwd(w, h, ids, corr, biasg, abs_mode, impl)[0]


def _fused_head_lse_fwd(w, h, ids, corr, biasg, abs_mode, impl):
    if impl == "pallas":
        lse = _fused_lse(w, h, ids, corr, biasg, abs_mode=abs_mode,
                         interpret=_interpret())
    else:
        lse = _chunked_lse(w, h, ids, corr, biasg, abs_mode)
    return lse, (w, h, ids, corr, biasg, lse)


def _fused_head_lse_bwd(abs_mode, impl, res, gbar):
    w, h, ids, corr, biasg, lse = res
    if impl == "pallas":
        dw, dh, dcoef, dcorr = _fused_lse_bwd(w, h, ids, corr, biasg, lse,
                                              gbar, abs_mode=abs_mode,
                                              interpret=_interpret())
    else:
        dw, dh, dcoef, dcorr = _chunked_lse_bwd(w, h, ids, corr, biasg, lse,
                                                gbar, abs_mode)
    return (dw.astype(w.dtype), dh.astype(h.dtype),
            np.zeros(ids.shape, jax.dtypes.float0),
            dcorr.astype(corr.dtype), dcoef.astype(biasg.dtype))


_fused_head_lse.defvjp(_fused_head_lse_fwd, _fused_head_lse_bwd)


def fused_head_lse(w: Array, h: Array, ids: Array, corr: Array,
                   biasg: Array | None = None, *, abs_mode: bool = False,
                   impl: str = "auto") -> Array:
    """Fused sampled-softmax head: per-token corrected logsumexp.  -> (T,).

    w: (n, d) head table; h: (T, d) hidden states; ids: (T, K) rows to
    gather; corr: (T, K) per-slot corrections SUBTRACTED after the abs-mode
    transform (0 for a positive slot, ``ln(m q)`` for a negative per eq. 2,
    ``MASK_CORR`` for accidental hits / padding — those slots contribute
    exactly zero mass and zero gradient); biasg: optional (T, K) pre-gathered
    class bias ADDED to the raw logit before the transform.

    Differentiable wrt w, h, corr, and biasg via ``jax.custom_vjp``: the
    backward scatter-adds dL/dw and accumulates dL/dh without materializing
    the (T, K, d) gather (kernels/fused_head.py).  ``impl``: "auto" picks the
    Pallas kernel on TPU (when the dL/dw accumulator fits VMEM) and the
    chunked jnp path elsewhere; "pallas"/"chunked" force a path ("pallas"
    off-TPU runs in interpret mode — correctness only)."""
    t, k = ids.shape
    if biasg is None:
        biasg = jnp.zeros((t, k), jnp.float32)
    impl = resolve_fused_impl(impl, *w.shape)
    return _fused_head_lse(w, h, ids.astype(jnp.int32),
                           corr.astype(jnp.float32),
                           biasg.astype(jnp.float32), bool(abs_mode), impl)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    q_tile: int = 128, kv_tile: int = 128) -> Array:
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) GQA -> (B, S, H, hd)."""
    b, s, h_heads, hd = q.shape
    kv = k.shape[2]
    group = h_heads // kv
    if group > 1:  # expand KV heads to match (GQA)
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    qt = jnp.moveaxis(q, 2, 1).reshape(b * h_heads, s, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * h_heads, s, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * h_heads, s, hd)
    q_tile = min(q_tile, s)
    kv_tile = min(kv_tile, s)
    qp, _ = _pad_to(qt, 1, q_tile)
    kp, _ = _pad_to(kt, 1, kv_tile)
    vp, _ = _pad_to(vt, 1, kv_tile)
    sp = max(qp.shape[1], kp.shape[1])
    qp, _ = _pad_to(qp, 1, sp)
    kp, _ = _pad_to(kp, 1, sp)
    vp, _ = _pad_to(vp, 1, sp)
    out = _flash(qp, kp, vp, causal=causal, q_tile=q_tile, kv_tile=kv_tile,
                 s_valid=s, interpret=_interpret())
    out = out[:, :s]
    return jnp.moveaxis(out.reshape(b, h_heads, s, hd), 1, 2)


# --- causal self-attention, forward + backward (jax's splash kernels) -------


def splash_block(s: int) -> int:
    """The size of every block of the splash kernels at sequence length s
    (a multiple of 128): the largest of 512, 256, 128 that divides it.
    At S 2048, 24 query and 2 KV heads of 128 on a TPU v5e, 512 with the
    fused backward beat 256, 1024 and mixed sizes (PERF.md, PR 14)."""
    return next(b for b in (512, 256, 128) if s % b == 0)


@functools.lru_cache(maxsize=16)
def _splash_kernel(s: int, group: int, interpret: bool):
    """The splash MQA kernel for one KV head and its ``group`` query heads
    under a causal mask, built once per static shape.  Its mask tables are
    made eagerly, so a kernel built while a step is traced holds no
    tracer."""
    b = splash_block(s)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * group)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=sizes, interpret=interpret)


def causal_attention(q: Array, k: Array, v: Array) -> Array:
    """Causal self-attention by the splash flash kernels (a forward and a
    fused dq + dkv backward behind a custom VJP; blocks above the diagonal
    skipped).

    q: (B, S, H, hd); k, v: (B, S, KV, hd) with H a multiple of KV (GQA)
    and S a multiple of 128 -> (B, S, H, hd).  q is scaled by 1/sqrt(hd)
    in float32 and rounded back to its dtype, so the MXU takes q and k in
    that dtype; each KV head is shared by its query group (no head
    expansion).  The softmax statistics and accumulators are float32
    inside the kernels."""
    b, s, h_heads, hd = q.shape
    kv = k.shape[2]
    group = h_heads // kv
    kernel = _splash_kernel(s, group, _interpret())
    q = (q * (1.0 / np.sqrt(hd))).astype(q.dtype)
    qt = jnp.moveaxis(q, 1, 2).reshape(b * kv, group, s, hd)
    kt = jnp.moveaxis(k, 1, 2).reshape(b * kv, s, hd)
    vt = jnp.moveaxis(v, 1, 2).reshape(b * kv, s, hd)
    out = jax.vmap(kernel)(qt, kt, vt)
    return jnp.moveaxis(out.reshape(b, h_heads, s, hd), 1, 2)
