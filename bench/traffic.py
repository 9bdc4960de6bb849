"""Traffic generators: one general generator per kind of traffic, driven
by the parameters in ``bench/workloads/<traffic>.json``.

Every generator is seeded.  Training batches are made on the device in
one jitted call at set-up, as a ring the window cycles through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def markov_lm_ring(key, *, vocab_size: int, batch: int, seq_len: int,
                   ring: int, rank: int = 16, temperature: float = 1.0):
    """``ring`` LM batches {tokens, labels: (batch, seq_len) int32}.

    An order-1 Markov language with low-rank transition logits,
    P(next | prev) ∝ exp(<E[next], C[prev]> * temperature / sqrt(rank)),
    the ``SyntheticLM`` of ``src/repro/data/synthetic.py`` with its tables
    drawn from ``key``; all ring batches are drawn in one scan."""

    def make(key):
        k_tab, k_first, k_seq = jax.random.split(key, 3)
        k1, k2 = jax.random.split(k_tab)
        e = jax.random.normal(k1, (vocab_size, rank))
        c = jax.random.normal(k2, (vocab_size, rank))
        scale = temperature / np.sqrt(rank)
        rows = ring * batch

        def step(prev, k):
            nxt = jax.random.categorical(k, (c[prev] @ e.T) * scale, axis=-1)
            return nxt, nxt

        first = jax.random.randint(k_first, (rows,), 0, vocab_size)
        _, seq = jax.lax.scan(step, first, jax.random.split(k_seq, seq_len))
        seq = jnp.moveaxis(seq, 0, 1)  # (rows, seq_len)
        tokens = jnp.concatenate([first[:, None], seq[:, :-1]], axis=1)
        shape = (ring, batch, seq_len)
        return [{"tokens": t, "labels": y} for t, y in zip(
            tokens.astype(jnp.int32).reshape(shape),
            seq.astype(jnp.int32).reshape(shape))]

    return jax.jit(make)(key)
