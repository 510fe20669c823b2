"""bench/trace_reduce.py on a small profiler trace recorded on the CPU."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three runs of a jitted step inside ``bench.window``, with a host-only
    ``bench.idle`` span between two of them."""
    import time

    step = jax.jit(lambda x: jnp.sort(x * 3.0) + 1.0)
    x = jnp.arange(1 << 18, dtype=jnp.float32)
    step(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            step(x).block_until_ready()
            if i == 1:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(0.05)
    jax.profiler.stop_trace()
    return trace_reduce.reduce_trace(trace_reduce.find_xplane(out))


def test_window_and_busy(traced):
    assert traced["devices"] == 1
    assert 0.05 < traced["window_s"] < 30
    assert 0 < traced["busy_s"] < traced["window_s"] - 0.04


def test_modules_count_runs(traced):
    seconds, runs = trace_reduce.module_time(traced, "jit__lambda")
    assert runs == 3
    assert 0 < seconds <= traced["busy_s"] + 1e-9


def test_idle_gap_named_by_host_span(traced):
    gaps = dict(traced["idle_gaps"])
    assert gaps.get("bench.idle", 0) >= 0.04
    assert sum(gaps.values()) == pytest.approx(
        traced["window_s"] - traced["busy_s"], rel=1e-6, abs=1e-9
    )
    assert traced["device_ops"] and len(traced["device_ops"]) <= 10


def test_missing_window_is_an_error(tmp_path):
    step = jax.jit(lambda x: x + 1)
    jax.profiler.start_trace(str(tmp_path))
    step(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_trace(trace_reduce.find_xplane(tmp_path))
