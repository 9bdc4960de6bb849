"""End-to-end LM training driver (deliverable b).

Trains a llama-style decoder LM with kernel-based sampled softmax on the
synthetic Markov language, reporting the true (full-softmax) eval loss
against the chain's entropy floor.  Presets scale the same driver from a
seconds-long smoke run to a ~100M-parameter run.

Run:  PYTHONPATH=src python examples/train_lm.py --preset small --steps 200
      PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.api import fit
from repro.configs import get_config
from repro.data.pipeline import batch_iterator_for
from repro.data.synthetic import SyntheticLM
from repro.optim import cosine_schedule, make_optimizer
from repro.sharding.rules import local_ctx
from repro.train.step import make_eval_fn
from repro.utils.compile_cache import enable_compile_cache

PRESETS = {
    # name: (d_model, layers, heads, kv, d_ff, vocab, seq, batch)
    "tiny": (64, 2, 4, 2, 128, 512, 32, 16),
    "small": (128, 4, 8, 4, 512, 4096, 64, 16),
    "100m": (512, 8, 8, 4, 2048, 32768, 256, 16),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--sampler", default="block-quadratic-shared")
    ap.add_argument("--estimator", default="sampled-softmax",
                    help="loss estimator over the sampled negatives "
                         "(sampled-softmax | nce | sampled-logistic | full)")
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    d, nl, nh, nkv, ff, vocab, seq, batch = PRESETS[args.preset]
    cfg = dataclasses.replace(
        get_config("llama3-8b"),
        name=f"llama-{args.preset}", vocab_size=vocab, d_model=d,
        n_layers=nl, n_heads=nh, n_kv_heads=nkv, head_dim=d // nh, d_ff=ff,
        sampler=args.sampler, m_negatives=args.m,
        estimator=args.estimator,
        sampler_block=256, sampler_proj_rank=None, microbatches=1,
        dtype="float32", param_dtype="float32", remat=False)

    ctx = local_ctx()
    opt = make_optimizer(
        "adamw", cosine_schedule(3e-3, warmup_steps=20,
                                 total_steps=args.steps))
    data = batch_iterator_for(cfg, ctx, global_batch=batch, seq_len=seq)
    lm_task = SyntheticLM(vocab_size=vocab)
    print(f"model: {cfg.name}  vocab={vocab}  sampler={cfg.sampler} "
          f"estimator={cfg.estimator} m={cfg.m_negatives}")
    print(f"chain entropy (loss floor): {lm_task.chain_entropy():.4f}")

    eval_batch = next(data)
    # The dense oracle (estimator="full") through the train step's island.
    eval_fn = jax.jit(make_eval_fn(cfg, ctx))

    def eval_loss(params):
        return eval_fn(params, eval_batch)

    t0 = time.time()
    res = fit(cfg, ctx, opt, data, steps=args.steps, log_every=20,
              checkpoint_dir=args.checkpoint_dir, max_len=seq,
              eval_fn=lambda st: float(eval_loss(st.params)))
    n_params = sum(int(jnp.size(x)) for x in
                   jax.tree_util.tree_leaves(res.state.params))
    print(f"\n{n_params/1e6:.1f}M params, {args.steps} steps in "
          f"{time.time()-t0:.0f}s")
    print(f"final full-softmax eval loss: {eval_loss(res.state.params):.4f} "
          f"(floor {lm_task.chain_entropy():.4f})")
    if res.straggler_steps:
        print(f"straggler steps detected: {res.straggler_steps}")


if __name__ == "__main__":
    main()
