"""Pallas TPU kernel: VMEM-resident k-full-sweep multispin update (S9).

Same resident-tier contract as ``kernels/stencil/resident.py`` -- both
packed word planes staged into VMEM once, ``n_sweeps`` full sweeps in an
in-kernel ``lax.fori_loop`` with Philox offsets advanced per (sweep,
color) by ``core.rng.half_sweep_offset``, one write-back -- applied to
the S2 nibble packing: 8 spins/uint32 word, three packed adds per
neighbor sum, two Philox4x32 calls per word (8 draws), and the H1.6
integer-threshold accept with the 10-entry table in SMEM (precomputed
once per call, structurally hoisted out of the in-kernel loop).

Bit-exact vs ``n_sweeps`` iterations of the ``core.multispin`` oracle
(``run_sweeps_packed``) -- the draw keys come from ``seed_keys`` exactly
as the oracle's ``word_randoms``, so full 64-bit python seeds match too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lattice as lat
from repro.core import rng as crng
from repro.kernels import resident as vmem
from repro.kernels.names import kernel_name

_NIB = lat.NIBBLE_BITS


def _half_sweep(target, op, is_black: bool, thr, k0, k1, offset,
                widx=None):
    """One packed color half-sweep on whole VMEM-resident word planes.

    ``widx`` overrides the Philox word keying with a precomputed uint32
    global word-index plane (sharded resident tier, ``repro.dist``);
    ``None`` keys on local iota -- correct when the planes ARE the full
    lattice."""
    up = jnp.concatenate([op[-1:, :], op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], op[:1, :]], axis=0)
    # side word: nibble funnel shift splicing the edge nibble of the
    # adjacent word (paper Fig. 3); column wrap as slice-concat (H1.4)
    nxt = jnp.concatenate([op[:, 1:], op[:, :1]], axis=1)
    prv = jnp.concatenate([op[:, -1:], op[:, :-1]], axis=1)
    plus = (op >> np.uint32(_NIB)) | (nxt << np.uint32(32 - _NIB))
    minus = (op << np.uint32(_NIB)) | (prv >> np.uint32(32 - _NIB))
    parity = jax.lax.broadcasted_iota(jnp.uint32, op.shape, 0) % np.uint32(2)
    if is_black:
        side = jnp.where(parity == 1, plus, minus)
    else:
        side = jnp.where(parity == 1, minus, plus)
    nn_words = up + down + op + side          # 3 packed adds / 8 spins

    if widx is None:
        w = op.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, op.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, op.shape, 1)
        widx = (rows * w + cols).astype(jnp.uint32)
    zero = jnp.zeros_like(widx)
    lo = crng.philox4x32(np.uint32(2) * offset, zero, widx, zero, k0, k1)
    hi = crng.philox4x32(np.uint32(2) * offset + np.uint32(1), zero, widx,
                         zero, k0, k1)
    draws = lo + hi  # 8 uint32 per word

    # integer-threshold accept (H1.6): select chain over the 10 SMEM
    # scalars, same uint32s as the oracle's jnp.take -- bit-exact
    flip_word = jnp.zeros_like(target)
    for nib in range(lat.SPINS_PER_WORD):
        sh = np.uint32(nib * _NIB)
        s = (target >> sh) & np.uint32(1)
        nn = (nn_words >> sh) & np.uint32(0xF)
        idx = s * np.uint32(5) + nn
        t = jnp.zeros_like(idx)
        for c in range(10):
            t = jnp.where(idx == np.uint32(c), thr[c], t)
        flip = (draws[nib] < t).astype(jnp.uint32)
        flip_word = flip_word | (flip << sh)
    return target ^ flip_word


def _kernel(seeds_ref, thr_ref, black_ref, white_ref, black_out,
            white_out, *, n_sweeps: int):
    k0 = seeds_ref[0]
    k1 = seeds_ref[1]
    start = seeds_ref[2]
    thr = [thr_ref[c] for c in range(10)]  # SMEM scalar reads, no gather

    def body(i, carry):
        b, w = carry
        b = _half_sweep(b, w, True, thr, k0, k1,
                        crng.half_sweep_offset(start, i, 0))
        w = _half_sweep(w, b, False, thr, k0, k1,
                        crng.half_sweep_offset(start, i, 1))
        return (b, w)

    b, w = jax.lax.fori_loop(0, n_sweeps, body,
                             (black_ref[...], white_ref[...]))
    black_out[...] = b
    white_out[...] = w


def multispin_sweeps_resident(black_words, white_words, inv_temp, *,
                              n_sweeps: int, seed=0, start_offset=0,
                              interpret: bool = False, thresholds=None):
    """``n_sweeps`` packed full sweeps in ONE dispatch, words resident.

    Bit-exact vs ``core.multispin.run_sweeps_packed`` at the same
    ``start_offset``.  ``thresholds`` takes a precomputed
    ``acceptance_thresholds(inv_temp)``; ``None`` computes it here (once
    per dispatch either way -- it rides to SMEM outside the loop).
    """
    assert n_sweeps >= 1, n_sweeps
    from repro.core import multispin as ms
    if thresholds is None:
        thresholds = ms.acceptance_thresholds(inv_temp)
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1, jnp.asarray(start_offset, jnp.uint32)])

    plane = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, n_sweeps=n_sweeps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (k0, k1, offset)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # acceptance thresholds
            plane,                                   # black words (resident)
            plane,                                   # white words (resident)
        ],
        out_specs=(plane, plane),
        out_shape=(jax.ShapeDtypeStruct(black_words.shape,
                                        black_words.dtype),
                   jax.ShapeDtypeStruct(white_words.shape,
                                        white_words.dtype)),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("multispin", "resident"),
    )(seeds, thresholds, black_words, white_words)
