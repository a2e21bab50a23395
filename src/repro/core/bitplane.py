"""Bitplane multi-spin coding: 32 independent replicas, 1 bit/spin/word.

The nibble engine (``core.multispin``) packs 8 *spatial* sites per uint32;
this module packs the other axis, following Block, Virnau & Preis
(arXiv:1007.3726): bit ``r`` of word ``(i, k)`` is the 0/1 spin of
**replica r** at compact site ``(i, k)``, so one ``(N, M/2)`` uint32 color
plane holds 32 complete, independently-evolving lattices.  Three levers
fall out of the layout (DESIGN.md S8):

* **Neighbor sums as carry-save adders** -- the 4-neighbor up-count
  (0..4) of all 32 replicas at a site is three *bitplanes* ``(n0, n1,
  n2)`` produced by a bit-sliced 4-input adder: 8 bitwise ops per word,
  i.e. 1/4 op per replica-spin (vs 3 packed adds per 8 spins for the
  nibble engine).
* **One shared Philox draw per site** -- all 32 replicas at a site
  consume the SAME uint32 draw (one Philox4x32 call per FOUR sites), a
  32x reduction in randomness cost over the nibble engine's
  draw-per-spin.  The chains remain individually exact Metropolis
  chains, but they are *correlated across replicas at equal
  (site, step)* -- see the shared-randoms caveat in DESIGN.md S8:
  replica series may be averaged (each is a valid estimator) but never
  treated as 32 fully independent streams when deriving error bars.
  The coupling also means identical configurations never separate, and
  below T_c replicas falling into the same magnetization well COALESCE
  into bit-identical lattices; the replica multiplier is real above and
  near T_c (where the extra samples matter) and void deep in the
  ordered phase -- use an Ensemble of distinct seeds there.
* **Bit-parallel accept** -- with the integer-domain 10-entry threshold
  table (``multispin.acceptance_thresholds``, H1.6) the accept for all
  32 replicas is ``OR_c(class_mask_c & broadcast(u < t_c))`` over the 10
  ``(s, nn)`` classes: pure boolean logic, zero ``exp``, zero per-spin
  extraction on the hot path.

The Pallas kernel in ``repro/kernels/bitplane`` executes this same
algorithm on VMEM tiles; this module is its bit-exact oracle (``ref.py``
delegates here).  The distributed variant is
``core.distributed.make_bitplane_ising_step``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import lattice as lat
from . import multispin as ms
from . import observables as obs
from . import rng as crng

N_REPLICAS = 32
# numpy scalar (not a jnp array) so Pallas kernel bodies see a
# literal, not a captured constant (same convention as core/rng.py)
_FULL = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# packing: replica axis <-> word bits
# ---------------------------------------------------------------------------

def pack_replicas(planes01: jax.Array) -> jax.Array:
    """(32, N, C) 0/1 planes -> (N, C) uint32 words, bit r = replica r."""
    assert planes01.shape[0] == N_REPLICAS, planes01.shape
    shifts = jnp.arange(N_REPLICAS, dtype=jnp.uint32)[:, None, None]
    return jnp.sum(planes01.astype(jnp.uint32) << shifts, axis=0,
                   dtype=jnp.uint32)


def unpack_replicas(words: jax.Array) -> jax.Array:
    """(N, C) uint32 words -> (32, N, C) 0/1 uint32 planes."""
    shifts = jnp.arange(N_REPLICAS, dtype=jnp.uint32)[:, None, None]
    return (words[None] >> shifts) & jnp.uint32(1)


def pack_lattices(fulls_pm1: jax.Array):
    """(32, N, M) +-1 replica lattices -> (black_words, white_words)."""
    black, white = jax.vmap(lat.split_checkerboard)(fulls_pm1)
    return (pack_replicas(lat.to_binary(black)),
            pack_replicas(lat.to_binary(white)))


def unpack_lattices(black_words, white_words, dtype=jnp.int8) -> jax.Array:
    """(N, W) word planes -> (32, N, M) +-1 replica lattices."""
    black = lat.from_binary(unpack_replicas(black_words), dtype)
    white = lat.from_binary(unpack_replicas(white_words), dtype)
    return jax.vmap(lat.merge_checkerboard)(black, white)


def replica_lattice(black_words, white_words, r: int,
                    dtype=jnp.int8) -> jax.Array:
    """The (N, M) +-1 lattice of ONE replica (cheap single-bit extract)."""
    sh = jnp.uint32(r)
    black = lat.from_binary((black_words >> sh) & jnp.uint32(1), dtype)
    white = lat.from_binary((white_words >> sh) & jnp.uint32(1), dtype)
    return lat.merge_checkerboard(black, white)


def broadcast_plane(plane01: jax.Array) -> jax.Array:
    """0/1 plane -> word plane with all 32 replicas equal to it."""
    return plane01.astype(jnp.uint32) * _FULL


# ---------------------------------------------------------------------------
# bit-sliced neighbor counting
# ---------------------------------------------------------------------------

def bit_count_neighbors(up, down, center, side):
    """Carry-save 4-input adder: the 3-bit neighbor up-count of all 32
    replicas in 8 bitwise ops.

    Returns bitplanes ``(n0, n1, n2)`` with per-replica count
    ``n0 + 2*n1 + 4*n2`` in 0..4 (so n2 implies n0 = n1 = 0).
    """
    t = up ^ down
    s = t ^ center                      # low bit of up+down+center
    k = (up & down) | (center & t)      # carry of up+down+center
    n0 = s ^ side
    k2 = s & side
    n1 = k ^ k2
    n2 = k & k2
    return n0, n1, n2


def neighbor_counts(op_words: jax.Array, is_black: bool):
    """(n0, n1, n2) count bitplanes from the opposite color plane.

    Same neighbor geometry as the compact-plane engines (one word per
    site): up/down rolls plus the row-parity side tap
    (:func:`lattice.side_shift` operates bitwise-transparently on words).
    """
    up = jnp.roll(op_words, 1, axis=0)
    down = jnp.roll(op_words, -1, axis=0)
    side = lat.side_shift(op_words, is_black)
    return bit_count_neighbors(up, down, op_words, side)


# ---------------------------------------------------------------------------
# shared randomness: ONE uint32 per site
# ---------------------------------------------------------------------------

def site_randoms(seed, n_rows: int, n_cols: int, offset) -> jax.Array:
    """One uint32 draw per site, shared by all 32 replicas in the word.

    One Philox4x32 call serves FOUR sites: counter = (offset, 0,
    site_index // 4, 0), lane = site_index % 4 in row-major site order --
    the cuRAND-style skip-ahead scheme of DESIGN.md S4, so checkpoint
    restarts and the distributed step (which recomputes the same
    (group, lane) per global site) reproduce the stream exactly.
    """
    assert n_cols % 4 == 0, "bitplane planes need a multiple-of-4 width"
    k0, k1 = crng.seed_keys(seed)
    g = jnp.arange(n_rows * n_cols // 4, dtype=jnp.uint32)
    z = jnp.zeros_like(g)
    r = crng.philox4x32(jnp.asarray(offset, jnp.uint32), z, g, z, k0, k1)
    return jnp.stack(r, axis=-1).reshape(n_rows, n_cols)


def lane_draws(group, lane, offset, k0, k1) -> jax.Array:
    """The :func:`site_randoms` draw of each site, from its Philox
    ``group`` (site // 4) and ``lane`` (site % 4) planes.

    Every site runs the call of its group and keeps its own lane: four
    times the Philox work of :func:`site_randoms`, the same bits, and no
    interleave of the four lanes into a minor dimension of 4, which
    Mosaic lays out badly.  The kernels draw this way.
    """
    zero = jnp.zeros_like(group)
    l0, l1, l2, l3 = crng.philox4x32(offset, zero, group, zero, k0, k1)
    return jnp.where(lane == 0, l0,
                     jnp.where(lane == 1, l1,
                               jnp.where(lane == 2, l2, l3)))


def site_groups(row0, shape):
    """uint32 (group, lane) planes of a row block at plane row ``row0``
    of a ``shape[1]``-wide plane (the keying of :func:`lane_draws`)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    group = (rows * (shape[1] // 4) + (cols >> 2)).astype(jnp.uint32)
    return group, (cols & 3).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# bit-parallel Metropolis accept
# ---------------------------------------------------------------------------

def flip_word_from_classes(target, counts, draws, thresholds) -> jax.Array:
    """``OR_c(class_mask_c & broadcast(u < t_c))`` over the 10 (s, nn)
    classes: the flip decision of all 32 replicas as pure boolean logic.

    ``thresholds`` is indexable by the static class id ``s * 5 + nn``
    (a (10,) uint32 array here; the Pallas kernel passes a list of SMEM
    scalar reads), so no gather ever materializes.
    """
    n0, n1, n2 = counts
    not_t, not_n0, not_n1, not_n2 = ~target, ~n0, ~n1, ~n2
    zero = np.uint32(0)
    flip = jnp.zeros_like(target)
    for s in (0, 1):
        s_mask = target if s else not_t
        for nn in range(5):
            mask = (s_mask
                    & (n0 if nn & 1 else not_n0)
                    & (n1 if nn & 2 else not_n1)
                    & (n2 if nn & 4 else not_n2))
            accept = jnp.where(draws < thresholds[s * 5 + nn], _FULL, zero)
            flip = flip | (mask & accept)
    return flip


def update_color_bitplane(target_words, op_words, inv_temp, is_black: bool,
                          seed, offset, thresholds=None) -> jax.Array:
    """One bitplane half-sweep of all 32 replicas.

    ``thresholds`` lets sweep loops hoist the acceptance table out of
    their ``fori_loop`` (H1.6); ``None`` computes it here.
    """
    if thresholds is None:
        thresholds = ms.acceptance_thresholds(inv_temp)
    counts = neighbor_counts(op_words, is_black)
    n, w = target_words.shape
    draws = site_randoms(seed, n, w, offset)
    return target_words ^ flip_word_from_classes(target_words, counts,
                                                 draws, thresholds)


@functools.partial(jax.jit, static_argnames=("n_sweeps", "seed"),
                   donate_argnums=(0, 1))
def run_sweeps_bitplane(black_words, white_words, inv_temp, n_sweeps: int,
                        seed: int = 0, start_offset=0):
    start_offset = jnp.uint32(start_offset)
    thresholds = ms.acceptance_thresholds(inv_temp)  # hoisted: once per call

    def body(i, carry):
        b, w = carry
        b = update_color_bitplane(b, w, inv_temp, True, seed,
                                  crng.half_sweep_offset(start_offset, i,
                                                         0), thresholds)
        w = update_color_bitplane(w, b, inv_temp, False, seed,
                                  crng.half_sweep_offset(start_offset, i,
                                                         1), thresholds)
        return (b, w)

    return jax.lax.fori_loop(0, n_sweeps, body,
                             (black_words, white_words))


# ---------------------------------------------------------------------------
# per-replica observables
# ---------------------------------------------------------------------------

def replica_observables(black_words, white_words) -> dict:
    """{"m": (32,), "e": (32,)} -- one value per replica lattice.

    Measurement path, not hot path: extracts one replica lattice at a
    time (``lax.map``) and applies the layout-independent full-lattice
    observables, so each entry is bit-identical to measuring that
    replica's lattice alone.  One at a time, because the float32 temporaries
    of all 32 at once (16 GB at 8192^2) do not fit a chip.
    """
    def one(r):
        full = replica_lattice(black_words, white_words, r)
        return obs.magnetization_full(full), obs.energy_per_spin_full(full)

    m, e = jax.lax.map(one, jnp.arange(N_REPLICAS, dtype=jnp.uint32))
    return {"m": m, "e": e}
