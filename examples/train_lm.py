"""End-to-end training driver: byte-level LM on the EPSM-filtered pipeline.

Trains a reduced smollm-135m-family model for a few hundred steps on CPU
(full 135M config selectable with --full on real hardware), with EPSM
blocklist filtering + fingerprint dedup in the data path, checkpointing,
straggler watchdog, and resume-on-restart.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import dataclasses
from pathlib import Path

import jax

from repro.compile_cache import configure_compile_cache
from repro.configs import reduced_config, get_arch
from repro.data import corpus
from repro.data.pipeline import LMDataPipeline, VOCAB
from repro.dist.fault_tolerance import StepWatchdog
from repro.models import transformer as tf
from repro.train.loop import TrainConfig, train
from repro.train.optimizer import AdamWConfig

# Shared by train_lm.py (writes) and serve_lm.py (reads); gitignored.
CKPT_DIR = Path(__file__).resolve().parents[1] / ".lm_ckpt"


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=str(CKPT_DIR))
    ap.add_argument("--full", action="store_true", help="full 135M config")
    args = ap.parse_args()

    if args.full:
        cfg = dataclasses.replace(get_arch("smollm-135m").make_config(), vocab=VOCAB)
    else:
        cfg = dataclasses.replace(
            reduced_config("smollm-135m"),
            vocab=VOCAB, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=256,
            q_chunk=args.seq, kv_chunk=args.seq, ce_chunk=args.seq,
        )

    # the paper's technique in the data plane: blocklist + dedup
    blocklist = [b"FORBIDDEN", b"<secret>"]
    docs = corpus.documents("english", 10_000, doc_len=4096, seed=0)
    pipe = LMDataPipeline(
        docs, seq_len=args.seq, batch_size=args.batch,
        blocklist=blocklist, dedup=True,
    )

    params = tf.init_params(jax.random.key(0), cfg)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"model: {n_params/1e6:.1f}M params  vocab={cfg.vocab}")

    tc = TrainConfig(
        steps=args.steps,
        log_every=10,
        ckpt_every=max(args.steps // 4, 25),
        ckpt_dir=args.ckpt_dir,
        opt=AdamWConfig(peak_lr=3e-3, warmup_steps=20, total_steps=args.steps),
    )
    wd = StepWatchdog(factor=5.0, policy="log")
    loss_fn = lambda p, b: tf.train_loss(p, cfg, b)
    params, _, hist = train(loss_fn, params, pipe, tc, watchdog=wd)
    print(f"\nfinal loss {hist[-1]:.4f} (start {hist[0]:.4f})")
    print(f"pipeline stats: {pipe.stats}")
    if wd.events:
        print(f"straggler events: {len(wd.events)}")


if __name__ == "__main__":
    main()
