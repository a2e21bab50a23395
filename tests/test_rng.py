"""Philox4x32-10 correctness: known-answer vectors + limb-multiply property."""
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import rng


def test_philox_kat_zero():
    out = rng.philox4x32(*[jnp.uint32(0)] * 6)
    assert [int(x) for x in out] == [0x6627E8D5, 0xE169C58D, 0xBC57AC4C,
                                     0x9B00DBD8]


def test_philox_counter_sensitivity():
    a = rng.philox4x32(jnp.uint32(0), jnp.uint32(0), jnp.uint32(1),
                       jnp.uint32(0), jnp.uint32(0), jnp.uint32(0))
    b = rng.philox4x32(*[jnp.uint32(0)] * 6)
    assert not all(int(x) == int(y) for x, y in zip(a, b))


@given(a=st.integers(0, 2**32 - 1), b=st.integers(0, 2**32 - 1))
@settings(max_examples=200, deadline=None)
def test_mulhilo_matches_uint64(a, b):
    hi, lo = rng._mulhilo32(jnp.uint32(a), jnp.uint32(b))
    full = np.uint64(a) * np.uint64(b)
    assert int(hi) == int(full >> np.uint64(32))
    assert int(lo) == int(full & np.uint64(0xFFFFFFFF))


def test_uniforms_in_range_and_deterministic():
    seq = jnp.arange(4096, dtype=jnp.uint32)
    u1 = rng.uniforms(123, seq, jnp.uint32(7))[0]
    u2 = rng.uniforms(123, seq, jnp.uint32(7))[0]
    assert (u1 == u2).all()
    assert float(u1.min()) >= 0.0 and float(u1.max()) < 1.0
    # mean of 4096 uniforms within 5 sigma
    assert abs(float(u1.mean()) - 0.5) < 5 * 0.2887 / 64


def test_uniforms_offset_advances_stream():
    seq = jnp.arange(64, dtype=jnp.uint32)
    u1 = rng.uniforms(1, seq, jnp.uint32(0))[0]
    u2 = rng.uniforms(1, seq, jnp.uint32(1))[0]
    assert not bool((u1 == u2).all())


def test_u32_to_float_matches_direct_cast_bitwise():
    """The Mosaic-safe split conversion rounds exactly like the direct
    uint32 -> float32 cast, on the edge cases and on random words."""
    edge = np.array([0, 1, 2**24 - 1, 2**24, 2**24 + 1, 2**31 - 1, 2**31,
                     2**31 + 1, 0xFFFFFF7F, 0xFFFFFF80, 0xFFFFFFFF],
                    np.uint32)
    rand = np.random.default_rng(0).integers(0, 2**32, 100_000,
                                             dtype=np.uint64)
    words = jnp.asarray(np.concatenate([edge, rand.astype(np.uint32)]))
    got = np.asarray(rng.u32_to_float(words)).view(np.uint32)
    want = np.asarray(words.astype(jnp.float32)).view(np.uint32)
    np.testing.assert_array_equal(got, want)
