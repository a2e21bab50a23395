"""Distributed Ising engine: shard_map pencil decomposition + ICI halos.

The paper (S4) distributes the lattice as horizontal slabs, one per GPU, and
lets unified memory fetch the two boundary rows over NVLink.  TPUs have no
unified memory; the TPU-native equivalent is an explicit halo exchange with
``lax.ppermute`` over the ICI torus -- constant bytes/device, so unlike the
paper's single-NVSwitch ceiling (16 GPUs) this scales to arbitrary pods.

Layout: the two compact color planes ``(N, M/2)`` are sharded as a 2-D
pencil grid -- rows over the (pod, data) ring, columns over the model ring.
Each half-sweep exchanges one row-halo in each vertical direction and one
column-halo in each horizontal direction (the column halo carries the
single boundary spin of the paper's Fig. 3 side-word logic).

Randomness is global-position-keyed Philox, so results are *independent of
the device grid* -- resharding to a different mesh reproduces the same
physics trajectory bit-for-bit (tested in tests/test_distributed.py).

Halo/bulk overlap (beyond-paper, DESIGN.md S6.4): the update is split into
an interior region that depends only on local data and 1-wide border strips
that consume the halos, so XLA's latency-hiding scheduler can run the
ppermutes concurrently with the interior update.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import metropolis as metro
from . import rng as crng


# ---------------------------------------------------------------------------
# multi-level ring shift over a product of mesh axes
# ---------------------------------------------------------------------------

def ring_shift(x: jax.Array, axis_names: Sequence[str], shift: int):
    """Shift x by one position around the ring formed by the product of
    ``axis_names`` (most-significant first).  shift=+1 receives from the
    previous ring position (use for a *top* halo), -1 from the next.

    Implemented as a cascade: permute the least-significant axis, then fix
    up the wrap positions with permutes over progressively more significant
    axes (DESIGN.md S5: this is how a (pod, data) slab ring is built from
    per-axis ppermutes; ppermute itself is single-axis).
    """
    assert shift in (+1, -1)
    names = list(axis_names)

    def perm(axis, val):
        n = jax.lax.axis_size(axis)
        pairs = [((i - shift) % n, i) for i in range(n)]
        return jax.lax.ppermute(val, axis, pairs)

    out = perm(names[-1], x)
    # positions that wrapped on the k-th axis also need the (k-1)-th hop
    for k in range(len(names) - 1, 0, -1):
        idx = jax.lax.axis_index(names[k])
        n = jax.lax.axis_size(names[k])
        at_wrap = (idx == 0) if shift == +1 else (idx == n - 1)
        cross = perm(names[k - 1], out)
        out = jnp.where(at_wrap, cross, out)
    return out


def _exchange_halos(op, row_axes, col_axes):
    """Return (top, bottom, left, right) halos of the opposite-color plane."""
    with jax.named_scope("halo_exchange"):
        top = ring_shift(op[-1:, :], row_axes, +1)    # last row of upper nbr
        bottom = ring_shift(op[:1, :], row_axes, -1)  # first row of lower nbr
        left = ring_shift(op[:, -1:], col_axes, +1)
        right = ring_shift(op[:, :1], col_axes, -1)
    return top, bottom, left, right


def _haloed_taps(op, halos):
    """(up, down, nxt, prv) neighbor taps of the local shard with the
    exchanged halo rows/columns spliced in.

    H1.4 (EXPERIMENTS.md S Perf): every shifted read is pad+slice (a
    fusible producer) and the halo row/column enters through an
    iota-mask select over a virtual broadcast -- no extended buffer, no
    concatenates -- so each color update stays one fusion whose HBM
    traffic is read(op) + read(target) + write(target).  Shared by the
    basic, packed, and bitplane distributed updates.
    """
    top, bottom, left, right = halos
    nl, wl = op.shape
    zero = jnp.zeros((), op.dtype)
    row_i = jax.lax.broadcasted_iota(jnp.int32, op.shape, 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, op.shape, 1)

    def shift(x, dr, dc):
        """out[i,j] = x[i+dr, j+dc], zero-filled out of range."""
        pad_cfg = [(max(-dr, 0), max(dr, 0), 0),
                   (max(-dc, 0), max(dc, 0), 0)]
        padded = jax.lax.pad(x, zero, pad_cfg)
        return jax.lax.slice(padded, (max(dr, 0), max(dc, 0)),
                             (max(dr, 0) + nl, max(dc, 0) + wl))

    up = jnp.where(row_i == 0, top, shift(op, -1, 0))
    down = jnp.where(row_i == nl - 1, bottom, shift(op, 1, 0))
    nxt = jnp.where(col_i == wl - 1, right, shift(op, 0, 1))   # (i, k+1)
    prv = jnp.where(col_i == 0, left, shift(op, 0, -1))        # (i, k-1)
    return up, down, nxt, prv


# ---------------------------------------------------------------------------
# halo-aware neighbor sums (basic int8 engine)
# ---------------------------------------------------------------------------

def _nn_with_halos(op, halos, is_black, row0_parity):
    """4-neighbor sums for the local shard given exchanged halos.

    ``row0_parity`` is the global parity of the shard's first row (0 if the
    per-shard row count is even, which mesh construction guarantees).
    int8 arithmetic throughout: 4-neighbor sums fit, avoiding 4x-wide
    intermediates if XLA materializes anything (H1.5, EXPERIMENTS.md).
    """
    up, down, plus, minus = _haloed_taps(op, halos)
    rows = (jnp.arange(op.shape[0]) + row0_parity) % 2
    rows = rows[:, None]
    if is_black:
        side = jnp.where(rows == 1, plus, minus)
    else:
        side = jnp.where(rows == 1, minus, plus)
    return up + down + op + side  # int8 arithmetic: |sum| <= 4


def _global_positions(shape, row_axes, col_axes):
    """Global (row, col) index arrays of the local shard's cells."""
    n_loc, m_loc = shape

    def multi_index(axes):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx

    r0 = multi_index(row_axes) * n_loc
    c0 = multi_index(col_axes) * m_loc
    rows = r0 + jnp.arange(n_loc, dtype=jnp.int32)[:, None]
    cols = c0 + jnp.arange(m_loc, dtype=jnp.int32)[None, :]
    return rows, cols


def update_color_dist(target, op, inv_temp, is_black, seed, offset,
                      global_cols: int, row_axes, col_axes):
    """One distributed half-sweep of the basic engine on the local shard."""
    halos = _exchange_halos(op, row_axes, col_axes)
    rows, cols = _global_positions(target.shape, row_axes, col_axes)
    nn = _nn_with_halos(op, halos, is_black, row0_parity=0)
    gidx = (rows * global_cols + cols).astype(jnp.uint32)
    u = crng.uniforms(seed, gidx, jnp.uint32(offset))[0]
    t = target.astype(jnp.int32)
    acc = jnp.exp(-2.0 * inv_temp * nn.astype(jnp.float32)
                  * t.astype(jnp.float32))
    return jnp.where(u < acc, -t, t).astype(target.dtype)


def sweep_dist(black, white, inv_temp, seed, sweep_index, global_cols,
               row_axes, col_axes):
    black = update_color_dist(black, white, inv_temp, True, seed,
                              crng.half_sweep_offset(0, sweep_index, 0),
                              global_cols, row_axes, col_axes)
    white = update_color_dist(white, black, inv_temp, False, seed,
                              crng.half_sweep_offset(0, sweep_index, 1),
                              global_cols, row_axes, col_axes)
    return black, white


# ---------------------------------------------------------------------------
# public factory
# ---------------------------------------------------------------------------

def make_ising_step(mesh, *, n: int, m: int, seed: int = 0,
                    n_sweeps: int = 1, row_axes=None, col_axes=None):
    """Build a jitted multi-device Ising sweep function for ``mesh``.

    Rows of the compact planes are sharded over ``row_axes`` (default: all
    mesh axes but the last), columns over ``col_axes`` (default: the last
    mesh axis).  Returns (step_fn, plane_sharding).
    """
    names = list(mesh.axis_names)
    row_axes = tuple(row_axes if row_axes is not None else names[:-1])
    col_axes = tuple(col_axes if col_axes is not None else names[-1:])
    half = m // 2
    rows_devs = 1
    for a in row_axes:
        rows_devs *= mesh.shape[a]
    cols_devs = 1
    for a in col_axes:
        cols_devs *= mesh.shape[a]
    assert n % rows_devs == 0 and (n // rows_devs) % 2 == 0, (
        "per-shard row count must be even so checkerboard parity is uniform")
    assert half % cols_devs == 0

    spec = P(row_axes, col_axes)
    sharding = jax.sharding.NamedSharding(mesh, spec)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, P(), P()),
        out_specs=(spec, spec),
        check_vma=False)
    def _sweeps(black, white, inv_temp, sweep0):
        def body(i, carry):
            b, w = carry
            return sweep_dist(b, w, inv_temp, seed, sweep0 + i, half,
                              row_axes, col_axes)
        return jax.lax.fori_loop(0, n_sweeps, body, (black, white))

    # plane buffers are donated: callers rebind (b, w = step(b, w, ...)),
    # so a sharded lattice never holds two copies per device in HBM
    return jax.jit(_sweeps, donate_argnums=(0, 1)), sharding


def make_packed_ising_step(mesh, *, n: int, m: int, seed: int = 0,
                           n_sweeps: int = 1, row_axes=None, col_axes=None):
    """Multispin (packed uint32 nibble) distributed sweep -- the paper's
    optimized engine on the full mesh.  Halos: one word-row per vertical
    direction, one word-column per horizontal direction (the column halo
    carries the paper's Fig. 3 boundary nibble).  Returns
    (jitted step(black, white, inv_temp, sweep0), word-plane sharding)."""
    from . import lattice as lat
    from . import multispin as ms

    names = list(mesh.axis_names)
    row_axes = tuple(row_axes if row_axes is not None else names[:-1])
    col_axes = tuple(col_axes if col_axes is not None else names[-1:])
    words = m // 2 // lat.SPINS_PER_WORD
    spec = P(row_axes, col_axes)
    nib = lat.NIBBLE_BITS

    def update_packed(target, op, is_black, offset, thresholds):
        halos = _exchange_halos(op, row_axes, col_axes)
        up, down, nxt, prv = _haloed_taps(op, halos)
        plus = (op >> jnp.uint32(nib)) | (nxt << jnp.uint32(32 - nib))
        minus = (op << jnp.uint32(nib)) | (prv >> jnp.uint32(32 - nib))
        rows = (jax.lax.broadcasted_iota(jnp.uint32, op.shape, 0)
                % jnp.uint32(2))
        side = jnp.where(rows == 1, plus, minus) if is_black \
            else jnp.where(rows == 1, minus, plus)
        nn_words = up + down + op + side
        rpos, cpos = _global_positions(target.shape, row_axes, col_axes)
        widx = (rpos * words + cpos).astype(jnp.uint32)
        draws = ms.word_randoms(seed, widx, offset)
        flip = jnp.zeros_like(target)
        for k in range(lat.SPINS_PER_WORD):
            sh = jnp.uint32(k * nib)
            s = (target >> sh) & jnp.uint32(1)
            nnk = (nn_words >> sh) & jnp.uint32(0xF)
            idx = (s * jnp.uint32(5) + nnk).astype(jnp.int32)
            t = jnp.take(thresholds, idx)   # integer-domain accept (H1.6)
            flip = flip | ((draws[k] < t).astype(jnp.uint32) << sh)
        return target ^ flip

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, P(), P()),
                       out_specs=(spec, spec), check_vma=False)
    def sweeps(black, white, inv_temp, sweep0):
        thresholds = ms.acceptance_thresholds(inv_temp)  # hoisted (H1.6)

        def body(i, carry):
            b, w = carry
            b = update_packed(b, w, True,
                              crng.half_sweep_offset(sweep0, i, 0),
                              thresholds)
            w = update_packed(w, b, False,
                              crng.half_sweep_offset(sweep0, i, 1),
                              thresholds)
            return b, w
        return jax.lax.fori_loop(0, n_sweeps, body, (black, white))

    return (jax.jit(sweeps, donate_argnums=(0, 1)),
            jax.sharding.NamedSharding(mesh, spec))


def make_bitplane_ising_step(mesh, *, n: int, m: int, seed: int = 0,
                             n_sweeps: int = 1, row_axes=None,
                             col_axes=None):
    """Bitplane (32 replicas/word, DESIGN.md S8) distributed sweep.

    Same ring-shift halo machinery as the other engines: one word-row
    per vertical direction, one word-column per horizontal direction
    (the side tap reads a whole neighbor word -- the bitplane layout
    keeps one word per site, so no sub-word splice is needed).  The
    shared per-site Philox draw is keyed on the *global* (site // 4,
    site % 4) pair, recomputed per local site with a lane select, so the
    step reproduces the single-device ``run_sweeps_bitplane`` trajectory
    bit-for-bit on any mesh (tests/test_bitplane.py).  Returns
    (jitted step(black, white, inv_temp, sweep0), word-plane sharding);
    the plane buffers are donated.
    """
    from . import bitplane as bp
    from . import multispin as ms

    names = list(mesh.axis_names)
    row_axes = tuple(row_axes if row_axes is not None else names[:-1])
    col_axes = tuple(col_axes if col_axes is not None else names[-1:])
    half = m // 2
    assert half % 4 == 0, "bitplane planes need a multiple-of-4 width"
    rows_devs = 1
    for a in row_axes:
        rows_devs *= mesh.shape[a]
    cols_devs = 1
    for a in col_axes:
        cols_devs *= mesh.shape[a]
    assert n % rows_devs == 0 and (n // rows_devs) % 2 == 0, (
        "per-shard row count must be even so checkerboard parity is uniform")
    assert half % cols_devs == 0
    spec = P(row_axes, col_axes)

    # static: when every shard's column range is 4-aligned (the common
    # case), whole draw groups are shard-local and one Philox call serves
    # 4 sites, exactly as core.bitplane.site_randoms; otherwise fall back
    # to a per-site call + lane select (4x the Philox work, same bits)
    aligned_cols = (half // cols_devs) % 4 == 0

    def site_draws(shape, offset):
        nl, wl = shape
        k0, k1 = crng.seed_keys(seed)
        off = jnp.asarray(offset, jnp.uint32)
        if aligned_cols:
            rpos, gcol = _global_positions((nl, wl // 4), row_axes,
                                           col_axes)
            g = (rpos * (half // 4) + gcol).astype(jnp.uint32)
            zg = jnp.zeros_like(g)
            lanes = crng.philox4x32(off, zg, g, zg, k0, k1)
            return jnp.stack(lanes, axis=-1).reshape(nl, wl)
        rpos, cpos = _global_positions(shape, row_axes, col_axes)
        g = (rpos * (half // 4) + cpos // 4).astype(jnp.uint32)
        lane = (cpos % 4).astype(jnp.uint32)
        zg = jnp.zeros_like(g)
        l0, l1, l2, l3 = crng.philox4x32(off, zg, g, zg, k0, k1)
        return jnp.where(lane == 0, l0,
                         jnp.where(lane == 1, l1,
                                   jnp.where(lane == 2, l2, l3)))

    def update_bitplane(target, op, is_black, offset, thresholds):
        halos = _exchange_halos(op, row_axes, col_axes)
        up, down, nxt, prv = _haloed_taps(op, halos)
        rpos, _ = _global_positions(target.shape, row_axes, col_axes)
        parity = (rpos % 2).astype(jnp.uint32)
        side = jnp.where(parity == 1, nxt, prv) if is_black \
            else jnp.where(parity == 1, prv, nxt)
        counts = bp.bit_count_neighbors(up, down, op, side)
        draws = site_draws(target.shape, offset)
        return target ^ bp.flip_word_from_classes(target, counts, draws,
                                                  thresholds)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, P(), P()),
                       out_specs=(spec, spec), check_vma=False)
    def sweeps(black, white, inv_temp, sweep0):
        thresholds = ms.acceptance_thresholds(inv_temp)  # hoisted (H1.6)

        def body(i, carry):
            b, w = carry
            b = update_bitplane(b, w, True,
                                crng.half_sweep_offset(sweep0, i, 0),
                                thresholds)
            w = update_bitplane(w, b, False,
                                crng.half_sweep_offset(sweep0, i, 1),
                                thresholds)
            return b, w
        return jax.lax.fori_loop(0, n_sweeps, body, (black, white))

    return (jax.jit(sweeps, donate_argnums=(0, 1)),
            jax.sharding.NamedSharding(mesh, spec))


def magnetization_dist(mesh, row_axes=None, col_axes=None):
    """shard_map'd magnetization (psum over the whole mesh)."""
    names = list(mesh.axis_names)
    row_axes = tuple(row_axes if row_axes is not None else names[:-1])
    col_axes = tuple(col_axes if col_axes is not None else names[-1:])
    spec = P(row_axes, col_axes)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
                       out_specs=P(), check_vma=False)
    def _mag(black, white):
        s = black.astype(jnp.float32).sum() + white.astype(jnp.float32).sum()
        s = jax.lax.psum(s, row_axes + col_axes)
        count = 2.0 * black.size * jax.lax.psum(1, row_axes + col_axes)
        return s / count

    return jax.jit(_mag)
