"""The text-side block fingerprints: ``hash_text_blocks`` against the
pattern-side ``hash_blocks`` of the same blocks, and the index build's
traced program free of gathers and strided slices."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.packing import fingerprint_weights, hash_blocks, hash_text_blocks

BETA = engine.EPSMC_BETA
ROW = 128 * BETA  # the bytes of one contracted row

SHAPES = [
    (5000,),  # (n,)
    (3, 5000),  # (B, n)
    (2, 0),  # empty texts
    (2, BETA - 1),  # n < beta: no block
    (2, ROW - 24),  # n < ROW: one padded row
    (2, 3 * ROW + 40),  # n % ROW != 0
    (3, 2 * ROW + 7),  # n % beta != 0
    (2, 4 * ROW),  # a multiple of ROW
    ((1 << 20) + 32,),  # a stream window's width
]


@pytest.mark.parametrize("kbits", [11, 17, 22])
@pytest.mark.parametrize("fill", ["random", "ones"])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_hash_text_blocks_equals_hash_blocks(shape, fill, kbits):
    """Bit for bit, so that the mod-2^32 wrap of the byte planes (all-0xFF
    text) and the widened kbits of bucketed plans are covered."""
    if fill == "ones":
        text = np.full(shape, 0xFF, np.uint8)
    else:
        text = np.random.RandomState(kbits).randint(0, 256, shape).astype(
            np.uint8
        )
    weights = fingerprint_weights(BETA)
    nb = shape[-1] // BETA
    blocks = text[..., : nb * BETA].reshape(shape[:-1] + (nb, BETA))
    want = np.asarray(hash_blocks(jnp.asarray(blocks), weights, kbits))
    got = np.asarray(
        jax.jit(hash_text_blocks, static_argnums=2)(
            jnp.asarray(text), weights, kbits
        )
    )
    assert got.dtype == np.int32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_build_index_has_no_gather_or_strided_slice():
    """The block fingerprints are a contraction over lane-dense rows: the
    index build lowers to no gather and to no slice with a stride."""
    window = jax.ShapeDtypeStruct((1, 4096), jnp.uint8)
    jaxpr = jax.make_jaxpr(engine.build_index)(window).jaxpr
    eqns = list(_eqns(jaxpr))
    assert any(e.primitive.name == "dot_general" for e in eqns)
    assert not [e for e in eqns if "gather" in e.primitive.name]
    strided = [
        e
        for e in eqns
        if e.primitive.name == "slice"
        and any(s > 1 for s in (e.params.get("strides") or ()))
    ]
    assert not strided
