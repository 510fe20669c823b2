"""Train a reduced DIN CTR model on synthetic Zipf-skewed behavior data and
then score a candidate set through the retrieval path.

    PYTHONPATH=src python examples/recsys_ctr.py --steps 100
"""

import argparse

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import configure_compile_cache
from repro.configs import reduced_config
from repro.data.recsys_data import make_batch
from repro.models import recsys as rs
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import AdamWConfig


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()

    cfg = reduced_config("din")
    params = rs.init_params(jax.random.key(0), cfg)

    def data():
        i = 0
        while True:
            yield make_batch(cfg, args.batch, seed=i)
            i += 1

    tc = TrainConfig(
        steps=args.steps, log_every=10, ckpt_every=10**9, ckpt_dir=None,
        opt=AdamWConfig(peak_lr=3e-3, warmup_steps=10, total_steps=args.steps,
                        weight_decay=0.0),
    )
    loss_fn = lambda p, b: rs.train_loss(p, cfg, b)
    params, _, hist = train(loss_fn, params, data(), tc)
    print(f"\nloss: {hist[0]:.4f} -> {hist[-1]:.4f}")

    # retrieval: one user vs 10k candidates
    user = {k: jnp.asarray(v[:1]) for k, v in make_batch(cfg, 4, seed=999).items()
            if k != "label"}
    cands = jnp.arange(10_000, dtype=jnp.int32) % cfg.item_vocab
    scores = rs.retrieval_scores(params, cfg, user, cands)
    top = np.argsort(np.asarray(scores))[::-1][:5]
    print(f"top-5 candidates: {list(top)}  scores {np.asarray(scores)[top].round(3)}")


if __name__ == "__main__":
    main()
