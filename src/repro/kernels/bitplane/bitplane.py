"""Pallas TPU kernel: bitplane multi-spin Metropolis update (DESIGN.md S8).

32 replica lattices live as 1-bit planes of uint32 VPU lanes; per grid
step the kernel stages a row block of the target plane plus three source
row blocks (i-1, i, i+1 with periodic modulo index_maps -- the same VMEM
staging as the stencil/multispin kernels), builds the 3-bit neighbor
counts with the carry-save adder, draws ONE shared Philox uint32 per
site in-kernel, and forms the flip word with the bit-parallel 10-class
threshold accept.  The 10 uint32 thresholds arrive in SMEM, precomputed
once per sweep call (H1.6); per-class reads are scalar, so no gather.

The pure-jnp oracle is ``repro.core.bitplane`` itself (``ref.py``
delegates there); the kernel reuses its ``bit_count_neighbors`` /
``flip_word_from_classes`` helpers verbatim, so bit-exactness at any
block size is by construction (tested in tests/test_bitplane.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitplane as bpc
from repro.kernels import resident as vmem
from repro.kernels.names import kernel_name

DEFAULT_BLOCK_ROWS = 256


def _kernel(seeds_ref, thr_ref, target_ref, op_m1_ref, op_0_ref,
            op_p1_ref, out_ref, *, is_black: bool, block_rows: int):
    op = op_0_ref[...]
    up_row = op_m1_ref[...][-1:, :]
    down_row = op_p1_ref[...][:1, :]
    up = jnp.concatenate([up_row, op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], down_row], axis=0)

    # row-parity side tap (block_rows is even, so parity is block-local)
    nxt = jnp.roll(op, -1, axis=1)
    prv = jnp.roll(op, 1, axis=1)
    parity = jax.lax.broadcasted_iota(jnp.uint32, op.shape, 0) % np.uint32(2)
    if is_black:
        side = jnp.where(parity == 1, nxt, prv)
    else:
        side = jnp.where(parity == 1, prv, nxt)
    counts = bpc.bit_count_neighbors(up, down, op, side)

    # one shared draw per site: counter = (offset, 0, site//4, 0), lane =
    # site%4 -- identical (group, lane) math to core.bitplane.site_randoms
    group, lane = bpc.site_groups(pl.program_id(0) * block_rows, op.shape)
    draws = bpc.lane_draws(group, lane, seeds_ref[2], seeds_ref[0],
                           seeds_ref[1])

    target = target_ref[...]
    thr = [thr_ref[c] for c in range(10)]  # SMEM scalar reads, no gather
    out_ref[...] = target ^ bpc.flip_word_from_classes(target, counts,
                                                       draws, thr)


def bitplane_update(target_words, op_words, inv_temp, *, is_black: bool,
                    seed: int = 0, offset=0,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: bool = False, thresholds=None):
    """One bitplane color half-sweep; bit-exact vs the core.bitplane oracle."""
    n, w = target_words.shape
    assert w % 4 == 0, "bitplane planes need a multiple-of-4 width"
    block_rows = min(block_rows, n)
    assert n % block_rows == 0 and block_rows % 2 == 0
    nb = n // block_rows

    if thresholds is None:
        thresholds = bpc.ms.acceptance_thresholds(inv_temp)
    # seed_keys handles python ints (full 64-bit split) and traced uint32
    # seeds (ensemble vmap) alike, exactly as the oracle does
    k0, k1 = bpc.crng.seed_keys(seed)
    seeds = jnp.stack([jnp.asarray(k0, jnp.uint32),
                       jnp.asarray(k1, jnp.uint32),
                       jnp.asarray(offset, jnp.uint32)])

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
        name=kernel_name("bitplane", "stream"),
    )(seeds, thresholds, target_words, op_words, op_words, op_words)
