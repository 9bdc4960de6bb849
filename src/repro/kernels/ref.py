"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def zstats_ref(w: Array) -> Array:
    """w: (n_blocks, B, r) -> (n_blocks, r, r) fp32 Gram sums."""
    w32 = w.astype(jnp.float32)
    return jnp.einsum("nbi,nbj->nij", w32, w32)


def block_scores_ref(h: Array, z: Array, cnt: Array, alpha: float) -> Array:
    """h: (T, r); z: (N, r, r); cnt: (N,) -> (T, N) kernel masses."""
    h32 = h.astype(jnp.float32)
    quad = jnp.einsum("nij,ti,tj->tn", z.astype(jnp.float32), h32, h32)
    return alpha * quad + cnt[None, :]


def leaf_dots_ref(h: Array, table: Array, idx: Array) -> Array:
    """h: (T, r); table: (L, B, r); idx: (T, m) -> (T, m, B) raw dot
    products (logits) of every row of each query's drawn leaves."""
    return jnp.einsum("tmbr,tr->tmb", table[idx].astype(jnp.float32),
                      h.astype(jnp.float32))


def leaf_scores_ref(h: Array, table: Array, idx: Array,
                    alpha: float) -> Array:
    """h: (T, r); table: (L, B, r); idx: (T, m) -> (T, m, B)
    quadratic-kernel scores alpha * dot^2 + 1."""
    return alpha * jnp.square(leaf_dots_ref(h, table, idx)) + 1.0


def midx_list_masses_ref(h: Array, c1: Array, c2: Array, codes: Array,
                         cnt: Array, alpha: float) -> Array:
    """Fused codeword-pair mass oracle (DESIGN.md §2.9).

    h: (T, d); c1: (K1, d); c2: (K2, d); codes: (P, 2); cnt: (P,)
    -> (T, P) masses cnt_j * (alpha * <h, c1[a1_j] + c2[a2_j]>^2 + 1)."""
    ct = (c1.astype(jnp.float32)[codes[:, 0]]
          + c2.astype(jnp.float32)[codes[:, 1]])          # (P, d)
    dots = h.astype(jnp.float32) @ ct.T                   # (T, P)
    return cnt[None, :] * (alpha * jnp.square(dots) + 1.0)


def rff_features_ref(w: Array, omega: Array, mask: Array, logshift,
                     tau: float) -> Array:
    """w: (L, B, d); omega: (D, d); mask: (L, B) -> (L, D) masked per-leaf
    sums of the positive RFF features (DESIGN.md §2.7)."""
    w32 = w.astype(jnp.float32)
    om = omega.astype(jnp.float32)
    dots = jnp.einsum("lbd,kd->lbk", w32, om) / jnp.sqrt(
        jnp.asarray(tau, jnp.float32))
    nrm = jnp.sum(w32 * w32, axis=-1, keepdims=True) / (2.0 * tau)
    feats = jnp.exp(dots - nrm - jnp.reshape(logshift, ()))
    feats = feats / jnp.sqrt(jnp.asarray(omega.shape[0], jnp.float32))
    return jnp.einsum("lbk,lb->lk", feats, mask.astype(jnp.float32))


def sampled_loss_ref(h: Array, w_neg: Array, logq: Array, pos_logit: Array,
                     m_total: int) -> Array:
    """Corrected sampled softmax with shared negatives (paper eq. 2-3).

    h: (T, d); w_neg: (m, d); logq: (m,); pos_logit: (T,) -> loss (T,)."""
    h32 = h.astype(jnp.float32)
    o_neg = h32 @ w_neg.astype(jnp.float32).T  # (T, m)
    o_adj = o_neg - logq[None, :] - np.log(m_total)
    allx = jnp.concatenate([pos_logit[:, None].astype(jnp.float32), o_adj],
                           axis=-1)
    return jax.nn.logsumexp(allx, axis=-1) - pos_logit.astype(jnp.float32)


def fused_lse_ref(w: Array, h: Array, ids: Array, corr: Array, biasg: Array,
                  abs_mode: bool = False) -> Array:
    """Dense oracle of the fused-head logsumexp (kernels/fused_head.py).

    w: (n, d); h: (T, d); ids/corr/biasg: (T, K) -> (T,) fp32
    logsumexp_k(transform(<h_t, w_{ids[t,k]}> + biasg[t,k]) - corr[t,k]).
    Materializes the (T, K, d) gather the kernel exists to avoid."""
    rows = w[ids].astype(jnp.float32)                       # (T, K, d)
    o = jnp.einsum("tkd,td->tk", rows, h.astype(jnp.float32)) + biasg
    tl = jnp.abs(o) if abs_mode else o
    return jax.nn.logsumexp(tl - corr, axis=-1)


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool
                        ) -> Array:
    """q,k,v: (B, S, H, hd) (MHA layout) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
