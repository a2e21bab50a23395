"""Pallas TPU kernel: multi-spin-coded Metropolis update (paper S3.3).

The TPU adaptation of the paper's flagship engine: 0/1 spins packed 4 bits
each into uint32 VPU lanes (8/word vs the paper's 16-per-uint64 -- the VPU
datapath is 32-bit), neighbor sums in THREE packed adds per word, Philox
drawn in-kernel (no random array traffic, cuRAND-style skip-ahead), and a
10-entry threshold LUT replacing per-spin ``exp`` (DESIGN.md S6.3).

Grid: row blocks of the packed word plane at full width, with periodic
neighbors supplied by modulo index_maps (i-1, i, i+1) -- the VMEM staging
that plays the role of the paper's shared-memory tile.  Per grid step the
VMEM working set is 4 row blocks + LUT; block_rows trades VMEM footprint
against grid overhead (swept in benchmarks/).
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

DEFAULT_BLOCK_ROWS = 256
_NIB = lat.NIBBLE_BITS


def _kernel(seeds_ref, thr_ref, target_ref, op_m1_ref, op_0_ref,
            op_p1_ref, out_ref, *, is_black: bool, block_rows: int):
    op = op_0_ref[...]
    up_row = op_m1_ref[...][-1:, :]
    down_row = op_p1_ref[...][:1, :]
    up = jnp.concatenate([up_row, op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], down_row], axis=0)

    # side word: splice the one boundary nibble (paper Fig. 3)
    nxt = jnp.roll(op, -1, axis=1)
    prv = jnp.roll(op, 1, axis=1)
    plus = (op >> np.uint32(_NIB)) | (nxt << np.uint32(32 - _NIB))
    minus = (op << np.uint32(_NIB)) | (prv >> np.uint32(32 - _NIB))
    parity = jax.lax.broadcasted_iota(jnp.uint32, op.shape, 0) % np.uint32(2)
    if is_black:
        side = jnp.where(parity == 1, plus, minus)
    else:
        side = jnp.where(parity == 1, minus, plus)
    nn_words = up + down + op + side          # 3 packed adds / 8 spins

    target = target_ref[...]
    k0 = seeds_ref[0]
    k1 = seeds_ref[1]
    offset = seeds_ref[2]
    i = pl.program_id(0)
    w = op.shape[1]
    rows = (i * block_rows
            + jax.lax.broadcasted_iota(jnp.int32, op.shape, 0))
    cols = jax.lax.broadcasted_iota(jnp.int32, op.shape, 1)
    widx = (rows * w + cols).astype(jnp.uint32)
    zero = jnp.zeros_like(widx)
    lo = crng.philox4x32(np.uint32(2) * offset, zero, widx, zero, k0, k1)
    hi = crng.philox4x32(np.uint32(2) * offset + np.uint32(1), zero, widx,
                         zero, k0, k1)
    draws = lo + hi  # 8 uint32 per word

    # integer-threshold accept (H1.6): the 10 uint32 thresholds live in
    # SMEM; the per-nibble lookup is a select chain over scalar reads
    # (Pallas kernels cannot vector-gather from SMEM) -- same uint32s as
    # the oracle's jnp.take, so bit-exactness is preserved
    thr = [thr_ref[c] for c in range(10)]
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
    out_ref[...] = target ^ flip_word


def multispin_update(target_words, op_words, inv_temp, *, is_black: bool,
                     seed: int = 0, offset=0,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = False, thresholds=None):
    """One packed color half-sweep; bit-exact vs core.multispin oracle.

    ``thresholds`` takes a precomputed ``acceptance_thresholds(inv_temp)``
    so sweep loops hoist the 10 exps out of their fori_loop (H1.6).
    """
    from repro.core import multispin as ms
    n, w = target_words.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0 and block_rows % 2 == 0
    nb = n // block_rows

    if thresholds is None:
        thresholds = ms.acceptance_thresholds(inv_temp)
    # seed may be a python int (full 64-bit key, like the oracle's
    # word_randoms) or a traced uint32 scalar (ensemble vmap)
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1, jnp.asarray(offset).astype(jnp.uint32)])

    row_spec = pl.BlockSpec((block_rows, w), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, is_black=is_black, block_rows=block_rows),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (k0, k1, offset)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # acceptance thresholds
            row_spec,
            pl.BlockSpec((block_rows, w), lambda i: ((i - 1) % nb, 0)),
            row_spec,
            pl.BlockSpec((block_rows, w), lambda i: ((i + 1) % nb, 0)),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(target_words.shape,
                                       target_words.dtype),
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("multispin", "stream"),
    )(seeds, thresholds, target_words, op_words, op_words, op_words)
