"""Ahead-of-time compiles of the main path for a described TPU v5e chip, at
the sizes chip_smoke.py runs: nothing runs, but the chip's compiler refuses
what would not fit or lower there.  Each compile takes seconds here.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and the suite's workers all
import this file."""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import engine, stream
from repro.serve import query_plane

GiB = 1 << 30
# one pattern per regime: EPSMa (m < 4), EPSMb (m < 16), EPSMc
MIXED = [b"err", b"oomkill!", b"connection refused"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    set_log_dir = "TPU_LOG_DIR" not in os.environ
    if set_log_dir:  # else the TPU compiler writes logs under /tmp
        os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        if set_log_dir:
            os.environ.pop("TPU_LOG_DIR", None)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    if set_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _index(n, sharding):
    return engine.TextIndex(
        packed=_shape((1, n), jnp.uint32, sharding),
        block_fp=_shape((1, n // engine.EPSMC_BETA), jnp.int32, sharding),
        lengths=_shape((1,), jnp.int32, sharding),
    )


def _plans(sharding):
    plans = engine.compile_patterns(MIXED, canonical=True)
    assert {p.regime for p in plans} == {"a", "b", "c"}
    return jax.tree.map(
        lambda x: _shape(np.shape(x), x.dtype, sharding), plans
    )


def _compile(lowered):
    compiled = lowered.compile()
    # the main path is plain XLA: no Pallas kernel in the program
    assert "tpu_custom_call" not in compiled.as_text()
    return compiled.memory_analysis()


def test_resident_index_build_1gib(one_chip):
    """The service's index build of a 1 GiB corpus: the text crosses as a
    flat row and the index holds 4.5 device bytes per corpus byte (packed
    u32 + 8-byte block fingerprints), not a padded (1, n) uint8 row."""
    n = GiB
    mem = _compile(query_plane._resident_index.lower(
        _shape((n,), jnp.uint8, one_chip), _shape((), jnp.int32, one_chip)
    ))
    assert mem.argument_size_in_bytes <= n + 4096
    assert mem.output_size_in_bytes <= 4.5 * n + 4096
    assert mem.temp_size_in_bytes <= 4 * n


def test_count_many_1gib_mixed_plan(one_chip):
    """count_many over a resident 1 GiB index with an a/b/c plan set scans
    it window by window: temporaries stay those of one window."""
    mem = _compile(engine.count_many_jit.lower(
        _index(GiB, one_chip), _plans(one_chip), k=0
    ))
    assert mem.temp_size_in_bytes <= 64 << 20


def test_match_many_64mib(one_chip):
    """match_many over a 64 MiB index: the (1, P, n) mask is the output
    (its P = 3 rows padded to the chip's 4-row tile of a bool array) and
    the window loop writes it in place."""
    n = 64 << 20
    mem = _compile(engine.match_many_jit.lower(
        _index(n, one_chip), _plans(one_chip), k=0
    ))
    assert mem.output_size_in_bytes <= 4 * n + 4096
    assert mem.temp_size_in_bytes <= 64 << 20


@pytest.mark.parametrize("window", [4 << 20, stream.MAX_CHUNK_BYTES])
def test_stream_fused_count_step(one_chip, window):
    """The StreamScanner's fused chunk step (seam gate inside the matchers)
    at the chunk size auto-sizing picks on the chip and at its ceiling."""
    i32 = _shape((), jnp.int32, one_chip)
    step = stream._jitted_count_step(True, True)
    mem = _compile(step.lower(
        _shape((len(MIXED),), jnp.int32, one_chip),
        _shape((window,), jnp.uint8, one_chip), i32, i32, _plans(one_chip),
        ov=24, k=None,
    ))
    assert mem.temp_size_in_bytes <= 16 * window
