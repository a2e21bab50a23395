"""Optimized multi-spin engine (paper S3.3), TPU-adapted, pure JAX reference.

Spins are 0/1 nibbles packed 8-per-uint32 (the TPU VPU analogue of the
paper's 16-per-uint64 -- see DESIGN.md S2).  Per target word the neighbor
sums cost THREE packed adds (vs 24 unpacked for 8 spins).  The Metropolis
accept compares the raw uint32 draw against a 10-entry *integer* threshold
LUT (H1.6) -- acceptance probabilities only take values
``exp(-2 beta (2s-1)(2 nn - 4))`` for ``s in {0,1}, nn in {0..4}``, so the
table is computed once per sweep call and the hot path does zero ``exp``
and zero draw->float conversion (beyond-paper: the paper evaluates exp on
the hot path).

Randomness is in-place counter-based Philox (cuRAND semantics): two
philox4x32 calls yield the 8 uint32 draws a word needs; the counter encodes
(half-sweep offset, word index) so the stream is launch-order independent
and checkpoint-restart continues it exactly.

The Pallas kernel in ``repro/kernels/multispin`` executes this same
algorithm on VMEM tiles; this module is its oracle (`ref.py` delegates here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import lattice as lat
from . import rng as crng

_NIB = lat.NIBBLE_BITS


def acceptance_table(inv_temp) -> jax.Array:
    """p[s * 5 + nn] = exp(-2 beta (2s-1)(2 nn - 4)), 10 entries."""
    s = jnp.arange(2, dtype=jnp.float32)[:, None]      # 0/1
    nn = jnp.arange(5, dtype=jnp.float32)[None, :]     # 0..4
    p = jnp.exp(-2.0 * inv_temp * (2.0 * s - 1.0) * (2.0 * nn - 4.0))
    return p.reshape(10)


def acceptance_prob(inv_temp, s_u32, nn_u32):
    """Closed-form acceptance: identical floats to acceptance_table[idx]
    (same expression, same op order) but pure-elementwise, so XLA fuses
    it into the surrounding bitwise chain instead of materializing a
    gather -- the S Perf H1.1 change (EXPERIMENTS.md)."""
    s = s_u32.astype(jnp.float32)
    nn = nn_u32.astype(jnp.float32)
    return jnp.exp(-2.0 * inv_temp * (2.0 * s - 1.0) * (2.0 * nn - 4.0))


def acceptance_thresholds(inv_temp) -> jax.Array:
    """The 10-entry acceptance table in the *integer* domain (H1.6).

    ``t[s * 5 + nn]`` is a uint32 threshold such that ``raw_u32_draw < t``
    accepts with probability ``min(1, p(s, nn))`` up to 2^-32 quantization:
    classes with p >= 1 (energy-lowering or neutral flips) map to
    0xFFFFFFFF, so they accept with probability 1 - 2^-32 -- statistically
    invisible, and what buys the hot path freedom from per-spin ``exp``
    *and* the uint32->float32 draw conversion.  Computed once per sweep
    call (10 exps), hoisted out of the fori_loop by the sweep wrappers.
    """
    p = acceptance_table(inv_temp)
    # p < 1 in float32 means p <= 1 - 2^-24, so p * 2^32 <= 2^32 - 256
    # fits uint32 exactly; astype truncates toward zero.
    scaled = p * jnp.float32(4294967296.0)
    return jnp.where(p < 1.0, scaled.astype(jnp.uint32),
                     jnp.uint32(0xFFFFFFFF))


def word_randoms(seed, word_index, offset):
    """8 uint32 draws per word: two Philox4x32 calls (cuRAND-style).

    ``seed`` may be a python int or a traced uint32 array (ensemble vmap).
    """
    k0, k1 = crng.seed_keys(seed)
    z = jnp.zeros_like(word_index)
    lo = crng.philox4x32(jnp.uint32(2 * offset), z, word_index, z, k0, k1)
    hi = crng.philox4x32(jnp.uint32(2 * offset + 1), z, word_index, z, k0, k1)
    return lo + hi  # tuple of 8 uint32 arrays


def update_color_packed(target_words, op_words, inv_temp, is_black: bool,
                        seed: int, offset, thresholds=None):
    """One packed half-sweep. target/op are (N, W) uint32 nibble words.

    The accept is a raw-uint32 compare against the precomputed
    :func:`acceptance_thresholds` table (H1.6): no per-spin ``exp``, no
    draw->float conversion.  ``thresholds`` lets sweep loops hoist the
    table out of their ``fori_loop``; ``None`` computes it here.
    """
    nn_words = lat.packed_neighbor_sums(op_words, is_black)
    n, w = target_words.shape
    widx = jnp.arange(n * w, dtype=jnp.uint32).reshape(n, w)
    draws = word_randoms(seed, widx, offset)
    if thresholds is None:
        thresholds = acceptance_thresholds(inv_temp)

    flip_word = jnp.zeros_like(target_words)
    for nib in range(lat.SPINS_PER_WORD):
        s = (target_words >> jnp.uint32(nib * _NIB)) & jnp.uint32(1)
        nn = (nn_words >> jnp.uint32(nib * _NIB)) & jnp.uint32(0xF)
        idx = (s * jnp.uint32(5) + nn).astype(jnp.int32)
        t = jnp.take(thresholds, idx)   # 10-entry table, integer domain
        flip = (draws[nib] < t).astype(jnp.uint32)
        flip_word = flip_word | (flip << jnp.uint32(nib * _NIB))
    return target_words ^ flip_word


@functools.partial(jax.jit, static_argnames=("n_sweeps", "seed"),
                   donate_argnums=(0, 1))
def run_sweeps_packed(black_words, white_words, inv_temp, n_sweeps: int,
                      seed: int = 0, start_offset=0):
    start_offset = jnp.uint32(start_offset)
    thresholds = acceptance_thresholds(inv_temp)   # hoisted: once per call

    def body(i, carry):
        b, w = carry
        b = update_color_packed(b, w, inv_temp, True, seed,
                                crng.half_sweep_offset(start_offset, i, 0),
                                thresholds)
        w = update_color_packed(w, b, inv_temp, False, seed,
                                crng.half_sweep_offset(start_offset, i, 1),
                                thresholds)
        return (b, w)

    return jax.lax.fori_loop(0, n_sweeps, body,
                             (black_words, white_words))


def pack_lattice(black_pm1, white_pm1):
    """+-1 compact planes -> packed uint32 word planes."""
    return (lat.pack_nibbles(lat.to_binary(black_pm1)),
            lat.pack_nibbles(lat.to_binary(white_pm1)))


def unpack_lattice(black_words, white_words, dtype=jnp.int8):
    return (lat.from_binary(lat.unpack_nibbles(black_words), dtype),
            lat.from_binary(lat.unpack_nibbles(white_words), dtype))


# ---------------------------------------------------------------------------
# observables straight from the packed words
# ---------------------------------------------------------------------------

_SPIN_MASK = 0x11111111   # bit 0 of every nibble: a word's eight spins
_LIMB_BITS = 12
_TOP = 32 - _NIB          # shift that brings a word's last nibble to bit 0


def _spin_count(words, mask: int = _SPIN_MASK):
    """Per-word count of set spins under ``mask`` (int32)."""
    return jax.lax.population_count(
        words & jnp.uint32(mask)).astype(jnp.int32)


def _exact_sum_f32(terms, bound: int):
    """float32 of the exact sum of int32 ``terms`` (last axis), rounded once.

    ``|terms| <= bound``.  Each term is split into a low limb of
    ``_LIMB_BITS`` bits and the rest, summed apart so that neither int32
    partial sum wraps; the low sum's carry then moves up, leaving
    total = hi * 2^12 + lo with 0 <= lo < 2^12.  float32(hi) is exact
    while |total| <= 2^36, so the one float add is the only rounding.
    """
    rows = terms.shape[-1]
    if rows << _LIMB_BITS >= 1 << 31 \
            or rows * ((bound >> _LIMB_BITS) + 2) >= 1 << 31:
        raise ValueError(f"{rows} terms of up to {bound} overflow the "
                         f"int32 limb sums")
    low = (1 << _LIMB_BITS) - 1
    lo = jnp.sum(terms & low, axis=-1)
    hi = jnp.sum(terms >> _LIMB_BITS, axis=-1) + (lo >> _LIMB_BITS)
    return (hi.astype(jnp.float32) * jnp.float32(1 << _LIMB_BITS)
            + (lo & low).astype(jnp.float32))


def spin_sum_from_counts(up_rows, width: int):
    """M = sum of the +-1 spins, float32 of the exact integer, from
    ``up_rows[i]`` in [0, width], the up spins of lattice row i.

    M = 2P - N is summed as per-row terms 2 p_i - width, centred on
    zero, so no partial sum wraps however large N is.
    """
    return _exact_sum_f32(2 * up_rows - width, width)


def bond_sum_from_counts(anti_rows, width: int):
    """B = sum over bonds of sigma_i sigma_j, float32 of the exact
    integer, from ``anti_rows[i]`` in [0, 2 width], the anti-aligned
    bonds among row i's ``width`` horizontal bonds and the ``width``
    vertical ones between rows i - 1 and i.

    With U anti-aligned bonds of 2N, B = 2N - 2U = -2 sum_i (u_i - width):
    per-row terms centred on zero again, and the doubling is exact.
    """
    return -2 * _exact_sum_f32(anti_rows - width, width)


def _spin_sum(black_words, white_words):
    up = _spin_count(black_words) + _spin_count(white_words)
    width = 2 * black_words.shape[1] * lat.SPINS_PER_WORD
    return spin_sum_from_counts(jnp.sum(up, axis=-1), width)


def _bond_sum(black_words, white_words):
    """B, each bond counted once, periodic as the update is.

    The neighbours are those of :func:`lattice.packed_neighbor_sums`,
    read through slices and in-word nibble shifts rather than rolled
    copies, so XLA reads the planes in place and writes no shifted copy.
    """
    b, w = black_words, white_words
    # vertical: both colours of row r against the other colour of row
    # r - 1 at the same compact column; row 0 wraps to the last row
    vert = jnp.sum(_spin_count(b[1:] ^ w[:-1])
                   + _spin_count(w[1:] ^ b[:-1]), axis=-1)
    vert0 = jnp.sum(_spin_count(b[:1] ^ w[-1:])
                    + _spin_count(w[:1] ^ b[-1:]), axis=-1)
    # horizontal: white at the same compact column, then the side
    # neighbour (lattice.side_shift): column k + 1 on odd rows, the next
    # nibble, k - 1 on even rows, the previous one; the nibble that
    # crosses into the adjacent word is counted from the word edges
    odd = (jnp.arange(b.shape[0]) % 2 == 1)[:, None]
    side = jnp.where(odd, _spin_count(b ^ (w >> _NIB), 0x01111111),
                     _spin_count(b ^ (w << _NIB), 0x11111110))
    inner = jnp.sum(_spin_count(b ^ w) + side, axis=-1)
    edge = jnp.sum(jnp.where(odd, (b[:, :-1] >> _TOP) ^ w[:, 1:],
                             b[:, 1:] ^ (w[:, :-1] >> _TOP)) & 1, axis=-1)
    edge0 = jnp.where(odd[:, 0], (b[:, -1] >> _TOP) ^ w[:, 0],
                      b[:, 0] ^ (w[:, -1] >> _TOP)) & 1
    anti = (jnp.concatenate([vert0, vert]) + inner
            + (edge + edge0).astype(jnp.int32))
    width = 2 * b.shape[1] * lat.SPINS_PER_WORD
    return bond_sum_from_counts(anti, width)


@jax.jit
def packed_sums(black_words, white_words):
    """(M, B) of packed (N, W) planes, with no lattice-sized int8 or
    float32 array; jitted so that an eager call on a large or sharded
    state runs as one program."""
    return (_spin_sum(black_words, white_words),
            _bond_sum(black_words, white_words))


_packed_spin_sum = jax.jit(_spin_sum)


def packed_observables(black_words, white_words) -> dict:
    """{"m": mean spin, "e": energy per spin} of packed (N, W) planes.

    m = M / N and e = -B / N from the exact sums of :func:`packed_sums`,
    so both equal the full-lattice observables
    (``observables.magnetization_full`` / ``energy_per_spin_full``) bit
    for bit wherever those are exact, up to 2^24 spins.  The division
    stays outside the jit, so it compiles as the full-lattice path's
    does in the same context.  Pure and vmap-safe.
    """
    spins, bonds = packed_sums(black_words, white_words)
    # a Python float: an int past 2^31 does not convert to a JAX scalar
    n_spins = float(2 * black_words.size * lat.SPINS_PER_WORD)
    return {"m": spins / n_spins, "e": -bonds / n_spins}


def packed_magnetization(black_words, white_words):
    """The "m" of :func:`packed_observables`, without the bond count."""
    n_spins = float(2 * black_words.size * lat.SPINS_PER_WORD)
    return _packed_spin_sum(black_words, white_words) / n_spins
