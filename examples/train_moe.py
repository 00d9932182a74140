"""Train a small MoE language model on the synthetic corpus.

Demonstrates the training substrate (data pipeline -> model -> AdamW ->
checkpointing). Defaults are CPU-sized; ``--preset 100m`` selects a
~100M-parameter GPT2-MoE for a real (longer) run.

Run:  PYTHONPATH=src python examples/train_moe.py --steps 30
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_params
from repro.config import get_arch, reduced_config
from repro.data.synthetic import SyntheticCorpus
from repro.device import enable_compile_cache
from repro.models import Model
from repro.optim import adamw_init, adamw_update, cosine_schedule


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    enable_compile_cache()

    base = get_arch("gpt2-moe")
    if args.preset == "100m":
        cfg = dataclasses.replace(base, vocab_size=32000, max_seq_len=512)
        seq, bsz = 256, 8
    else:
        cfg = reduced_config(base, num_blocks=base.num_blocks,
                             d_model=128, vocab=2048)
        cfg = dataclasses.replace(cfg, max_seq_len=256)
        seq, bsz = 64, 8
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params / 1e6:.1f}M params")

    opt = adamw_init(params)
    corpus = SyntheticCorpus(cfg.vocab_size, seq, bsz)

    @jax.jit
    def step(params, opt, batch, lr):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        params, opt = adamw_update(grads, opt, params, lr=lr)
        return params, opt, loss

    t0 = time.time()
    for i, raw in enumerate(corpus.batches(args.steps)):
        lr = cosine_schedule(i, peak_lr=3e-3, warmup_steps=10,
                             total_steps=args.steps)
        batch = {"tokens": jnp.asarray(raw["tokens"]),
                 "labels": jnp.asarray(raw["labels"])}
        params, opt, loss = step(params, opt, batch, lr)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}  "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    if args.ckpt:
        save_params(args.ckpt, params)
        print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
