"""Substrate tests: data pipeline (EPSM filter/dedup), corpus, optimizer,
checkpointing (atomic/resume/elastic), watchdog, gradient compression."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data import corpus
from repro.data.pipeline import BOS, LMDataPipeline
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


def test_corpus_generators():
    for name in ("genome", "protein", "english"):
        t = corpus.make_corpus(name, 10_000, seed=1)
        assert t.dtype == np.uint8 and len(t) == 10_000
        t2 = corpus.make_corpus(name, 10_000, seed=1)
        np.testing.assert_array_equal(t, t2)  # deterministic
    g = corpus.make_corpus("genome", 1000)
    assert set(np.unique(g)) <= set(b"ACGT")


def test_pipeline_blocklist_filter():
    bad = b"GATTACA"
    docs = []
    for i in range(40):
        d = corpus.make_corpus("genome", 512, seed=i)
        if i % 4 == 0:  # plant the blocked pattern
            d = d.copy()
            d[100:107] = np.frombuffer(bad, np.uint8)
        docs.append(d)
    pipe = LMDataPipeline(docs, seq_len=128, batch_size=2, blocklist=[bad])
    batches = list(pipe)
    assert pipe.stats.docs_blocked == 10
    assert pipe.stats.docs_out == 30
    for b in batches:
        assert b["tokens"].shape == (2, 128)
        assert b["tokens"].max() <= BOS
        # the blocked pattern never reaches training data
        flat = b["tokens"].astype(np.uint8).reshape(-1)
        from repro.core import epsm

        assert int(epsm.count(flat, np.frombuffer(bad, np.uint8))) == 0


def test_pipeline_dedup():
    base = corpus.make_corpus("english", 1024, seed=7)
    docs = [base, base.copy(), corpus.make_corpus("english", 1024, seed=8)]
    pipe = LMDataPipeline(docs, seq_len=64, batch_size=1, dedup=True)
    list(pipe)
    assert pipe.stats.docs_deduped == 1


def test_adamw_converges_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    cfg = AdamWConfig(peak_lr=0.2, warmup_steps=5, total_steps=200, weight_decay=0.0)
    loss = lambda p: jnp.sum(jnp.square(p["w"] - 1.0))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 1e-2


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones(5, jnp.bfloat16)}}
    ckpt.save(tree, tmp_path, step=10)
    ckpt.save(tree, tmp_path, step=20)
    restored, step = ckpt.restore(tree, tmp_path)
    assert step == 20
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16
    # keep-K GC
    for s in (30, 40, 50):
        ckpt.save(tree, tmp_path, step=s, keep=2)
    assert ckpt.latest_step(tmp_path) == 50
    import pathlib

    assert len(list(pathlib.Path(tmp_path).glob("step_*"))) == 2


def test_checkpoint_async_and_atomic(tmp_path):
    tree = {"w": jnp.ones((64, 64))}
    t = ckpt.save(tree, tmp_path, step=1, async_=True)
    t.join()
    restored, step = ckpt.restore(tree, tmp_path)
    assert step == 1
    # no stray tmp dirs after publish
    import pathlib

    assert not list(pathlib.Path(tmp_path).glob(".tmp_*"))


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint saved unsharded restores onto a sharded layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(tree, tmp_path, step=5)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ckpt.restore(tree, tmp_path, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


def test_watchdog_detects_straggler():
    import time

    from repro.dist.fault_tolerance import StepWatchdog, StragglerAbort

    wd = StepWatchdog(factor=5.0, policy="raise")
    for s in range(6):
        wd.start_step(s)
        time.sleep(0.003)
        wd.end_step()
    wd.start_step(6)
    time.sleep(0.1)
    with pytest.raises(StragglerAbort):
        wd.end_step()
    assert wd.events and wd.events[0].step == 6


def test_run_with_retries_classifies_errors():
    """Transient I/O retries up to the budget; programming errors and
    FatalScanError re-raise on the FIRST attempt — a TypeError from plan
    construction must not burn retries behind backoff."""
    from repro.dist.fault_tolerance import (
        FatalScanError,
        run_with_retries,
    )

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError("transient")
        return "ok"

    seen = []
    assert (
        run_with_retries(flaky, retries=5, on_failure=lambda a, e: seen.append(a))
        == "ok"
    )
    assert calls["n"] == 3 and seen == [0, 1]

    for exc_type in (TypeError, ValueError, KeyError, FatalScanError):
        calls = {"n": 0}

        def fatal():
            calls["n"] += 1
            raise exc_type("bad plan")

        with pytest.raises(exc_type):
            run_with_retries(fatal, retries=5)
        assert calls["n"] == 1  # no retry budget burned

    # a custom classifier overrides the default
    calls = {"n": 0}

    def vflaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise ValueError("transiently malformed")
        return "ok"

    assert (
        run_with_retries(
            vflaky, retries=3, is_retryable=lambda e: isinstance(e, ValueError)
        )
        == "ok"
    )
    assert calls["n"] == 2


def test_run_with_retries_backoff_schedule():
    """Delays follow the jittered exponential policy exactly (seeded), cap
    at max_s, and the final failing attempt sleeps nothing."""
    from repro.dist.fault_tolerance import BackoffPolicy, run_with_retries

    delays = []

    def always():
        raise IOError("down")

    with pytest.raises(IOError):
        run_with_retries(
            always,
            retries=4,
            backoff=BackoffPolicy(base_s=0.1, factor=2.0, max_s=0.3, jitter=0.5, seed=3),
            sleep=delays.append,
        )
    assert len(delays) == 4  # one per retried attempt, none after the last
    ref = BackoffPolicy(base_s=0.1, factor=2.0, max_s=0.3, jitter=0.5, seed=3)
    assert delays == pytest.approx([ref.delay_s(a) for a in range(4)])
    # jitterless policy is the pure exponential with a cap
    flat = BackoffPolicy(base_s=0.1, factor=2.0, max_s=0.3, jitter=0.0)
    assert [flat.delay_s(a) for a in range(4)] == pytest.approx(
        [0.1, 0.2, 0.3, 0.3]
    )
    with pytest.raises(ValueError):
        BackoffPolicy(jitter=2.0)


def test_gradient_compression_accuracy():
    """int8+EF quantized psum ~= exact psum, and EF kills the bias over steps."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.compression import quantized_psum, zeros_residuals

    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.RandomState(0).randn(128, 8), jnp.float32)}
    res = zeros_residuals(g)

    def f(g, r):
        return quantized_psum(g, r, "data")

    out, new_res = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )(g, res)
    rel = np.abs(np.asarray(out["w"]) - np.asarray(g["w"])).max() / np.abs(
        np.asarray(g["w"])
    ).max()
    assert rel < 1e-2  # single quantization step error bound
    # error feedback: residual + dequantized == original exactly
    recon = np.asarray(out["w"]) + np.asarray(new_res["w"])
    np.testing.assert_allclose(recon, np.asarray(g["w"]), rtol=0, atol=1e-6)
