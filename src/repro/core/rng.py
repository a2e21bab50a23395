"""Counter-based Philox4x32-10 RNG in pure uint32 jnp ops.

The paper's optimized and tensor-core engines use cuRAND's Philox4x32_10
device API with explicit (seed, sequence, offset) skip-ahead so that no RNG
state is ever stored in global memory.  We reproduce exactly that scheme:
``philox4x32(counter, key)`` is a pure function of a 4-lane uint32 counter and
a 2-lane uint32 key, implemented with 16-bit-limb multiplies so it runs
without 64-bit types -- which means the *same* code executes inside Pallas
TPU kernel bodies (VPU uint32 lanes) and in pure-jnp reference paths.

Skip-ahead semantics mirror ``curand_init(seed, sequence, offset)``:
``sequence`` selects the counter high lanes, ``offset`` the low lanes, so any
(step, position) pair addresses an independent 128-bit counter block yielding
4 uint32s.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy scalars (not jnp arrays) so Pallas kernel bodies see literals,
# not captured constants
PHILOX_M0 = np.uint32(0xD2511F53)
PHILOX_M1 = np.uint32(0xCD9E8D57)
PHILOX_W0 = np.uint32(0x9E3779B9)
PHILOX_W1 = np.uint32(0xBB67AE85)

_LO16 = np.uint32(0xFFFF)

#: half-sweeps per full lattice sweep -- the unit of the Philox offset
#: counter.  Every sweep loop in the repo (host-side fori_loops, the
#: per-half-sweep Pallas wrappers, AND the in-kernel loops of the
#: resident-sweep tier, DESIGN.md S9) advances its offset with
#: :func:`half_sweep_offset`, so the counter layout cannot fork between
#: host-side and in-kernel advancement.
HALF_SWEEPS_PER_SWEEP = 2


def half_sweep_offset(start_offset, sweep, color):
    """Philox offset of half-sweep ``color`` (0 = black, 1 = white) of
    full sweep ``sweep`` past a cumulative ``start_offset``.

    ``start_offset`` itself is in half-sweep units (= 2 x sweeps already
    run, cuRAND's ``offset``); all args may be python ints or traced
    uint32 scalars.  uint32 wrap-around is the cuRAND behavior, kept.
    """
    return (jnp.asarray(start_offset, jnp.uint32)
            + np.uint32(HALF_SWEEPS_PER_SWEEP) * jnp.asarray(sweep,
                                                             jnp.uint32)
            + jnp.asarray(color, jnp.uint32))


def _mulhilo32(a, b):
    """32x32 -> (hi, lo) uint32 multiply via 16-bit limbs (no uint64)."""
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    lo = a * b  # wrapping low half is exact
    a0 = a & _LO16
    a1 = a >> 16
    b0 = b & _LO16
    b1 = b >> 16
    a0b0 = a0 * b0
    a0b1 = a0 * b1
    a1b0 = a1 * b0
    a1b1 = a1 * b1
    # carry out of the middle 32 bits
    mid = (a0b1 & _LO16) + (a1b0 & _LO16) + (a0b0 >> 16)
    hi = a1b1 + (a0b1 >> 16) + (a1b0 >> 16) + (mid >> 16)
    return hi, lo


def _philox_round(c0, c1, c2, c3, k0, k1):
    hi0, lo0 = _mulhilo32(PHILOX_M0, c0)
    hi1, lo1 = _mulhilo32(PHILOX_M1, c2)
    return (hi1 ^ c1 ^ k0, lo1, hi0 ^ c3 ^ k1, lo0)


def philox4x32(c0, c1, c2, c3, k0, k1, rounds: int = 10):
    """Philox4x32-`rounds`. All args broadcastable uint32 arrays.

    Returns 4 uint32 arrays of the broadcast shape.
    """
    c0 = jnp.asarray(c0, jnp.uint32)
    c1 = jnp.asarray(c1, jnp.uint32)
    c2 = jnp.asarray(c2, jnp.uint32)
    c3 = jnp.asarray(c3, jnp.uint32)
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    for r in range(rounds):
        if r > 0:
            k0 = k0 + PHILOX_W0
            k1 = k1 + PHILOX_W1
        c0, c1, c2, c3 = _philox_round(c0, c1, c2, c3, k0, k1)
    return c0, c1, c2, c3


def seed_keys(seed):
    """Split a seed into the two Philox key lanes ``(k0, k1)``.

    Accepts either a python int (full 64-bit split, cuRAND semantics) or a
    traced uint32 array (high lane zero) -- the latter is what lets the
    ensemble driver ``vmap`` a batch of per-replica seeds through the same
    compiled sweep (DESIGN.md S4).
    """
    if isinstance(seed, (int, np.integer)):
        return (jnp.uint32(seed & 0xFFFFFFFF),
                jnp.uint32((seed >> 32) & 0xFFFFFFFF))
    seed = jnp.asarray(seed).astype(jnp.uint32)
    return seed, jnp.zeros_like(seed)


def uniforms(seed, sequence, offset, n_lanes: int = 4):
    """cuRAND-style draw: (seed, sequence, offset) -> 4 uniform floats in [0,1).

    ``sequence``/``offset`` are uint32 arrays (e.g. linear thread index and a
    per-launch monotonically increasing offset).  Matches the paper's scheme
    where every kernel launch re-inits Philox with the same seed, the thread's
    grid index as sequence, and the cumulative draw count as offset.
    ``seed`` may be a python int or a traced uint32 array (:func:`seed_keys`).
    """
    seq = jnp.asarray(sequence, jnp.uint32)
    off = jnp.asarray(offset, jnp.uint32)
    k0, k1 = seed_keys(seed)
    r0, r1, r2, r3 = philox4x32(off, jnp.zeros_like(seq), seq,
                                jnp.zeros_like(seq), k0, k1)
    return tuple(u32_to_uniform(r) for r in (r0, r1, r2, r3))[:n_lanes]


def u32_to_float(bits):
    """uint32 -> float32, bit for bit ``bits.astype(float32)``.

    Mosaic has no uint32 -> float32 cast, so the word is split into two
    16-bit halves that int32 -> float32 converts exactly; ``hi * 2^16``
    is exact too, so the one rounding is in the final add, the same
    round-to-nearest-even the direct cast does."""
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & _LO16).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def u32_to_uniform(bits):
    """uint32 -> float32 uniform in [0, 1) (multiply by 2^-32)."""
    return u32_to_float(bits) * jnp.float32(2.3283064365386963e-10)
