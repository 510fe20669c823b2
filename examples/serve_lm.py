"""Batched serving with EPSM stop-string detection.

Loads the checkpoint written by examples/train_lm.py when present (otherwise
random init) and serves a batch of prompts, stopping generation when an EPSM
packed scan finds a stop sequence in the generated bytes.

    PYTHONPATH=src python examples/serve_lm.py --max-new 48
"""

import argparse
import dataclasses
from pathlib import Path

import jax

from repro.compile_cache import configure_compile_cache
from repro.configs import reduced_config
from repro.data.pipeline import VOCAB
from repro.models import transformer as tf
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt
from repro.train.optimizer import adamw_init

# Shared by train_lm.py (writes) and serve_lm.py (reads); gitignored.
CKPT_DIR = Path(__file__).resolve().parents[1] / ".lm_ckpt"


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--ckpt-dir", default=str(CKPT_DIR))
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        reduced_config("smollm-135m"),
        vocab=VOCAB, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=256,
        q_chunk=128, kv_chunk=128, ce_chunk=128,
    )
    params = tf.init_params(jax.random.key(0), cfg)
    if ckpt.latest_step(args.ckpt_dir) is not None:
        (params, _), step = ckpt.restore((params, adamw_init(params)), args.ckpt_dir)
        print(f"loaded checkpoint at step {step}")
    else:
        print("no checkpoint found — serving the random-init model")

    eng = ServeEngine(params, cfg, max_len=256)
    prompts = [b"the quick brown ", b"people of the st", b"there would be a"]
    stops = [b". ", b"the end"]
    results = eng.generate(
        prompts, max_new_tokens=args.max_new,
        temperature=args.temperature, stop_strings=stops,
    )
    for p, r in zip(prompts, results):
        print(f"\nprompt: {p!r}")
        print(f"  -> {r.text!r}  (stopped_by={r.stopped_by!r})")


if __name__ == "__main__":
    main()
