"""Pallas TPU kernel: VMEM-resident k-full-sweep bitplane update (S9).

Same resident-tier contract as the stencil/multispin resident kernels
-- both uint32 bit planes (32 replica lattices deep, DESIGN.md S8)
staged into VMEM once, ``n_sweeps`` full sweeps in an in-kernel
``lax.fori_loop``, Philox offsets advanced per (sweep, color) by
``core.rng.half_sweep_offset``, one write-back.  Per half-sweep the
body reuses the oracle's helpers verbatim: carry-save neighbor counts
(``bit_count_neighbors``), ONE shared draw per site (counter =
(offset, 0, site//4, 0), lane = site%4 -- identical (group, lane) math
to ``core.bitplane.site_randoms``), and the bit-parallel 10-class
threshold accept (``flip_word_from_classes``) with the thresholds in
SMEM -- so bit-exactness vs ``n_sweeps`` iterations of
``run_sweeps_bitplane`` is by construction (tests/test_resident.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitplane as bpc
from repro.core import rng as crng
from repro.kernels import resident as vmem
from repro.kernels.names import kernel_name


def _half_sweep(target, op, is_black: bool, thr, k0, k1, offset,
                gidx=None, lane=None):
    """One bitplane half-sweep of all 32 replicas, planes resident.

    ``gidx``/``lane`` override the shared-draw keying with precomputed
    uint32 global (site // 4, site % 4) planes -- the sharded resident
    tier (``repro.dist``) uses them because its halo-extended shard
    columns are neither 0-based nor 4-aligned.  Either way each site
    draws through ``core.bitplane.lane_draws`` (same (group, lane) math
    as ``core.bitplane.site_randoms``, same bits)."""
    up = jnp.concatenate([op[-1:, :], op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], op[:1, :]], axis=0)
    nxt = jnp.concatenate([op[:, 1:], op[:, :1]], axis=1)
    prv = jnp.concatenate([op[:, -1:], op[:, :-1]], axis=1)
    parity = (jax.lax.broadcasted_iota(jnp.uint32, op.shape, 0)
              % jnp.uint32(2))
    if is_black:
        side = jnp.where(parity == 1, nxt, prv)
    else:
        side = jnp.where(parity == 1, prv, nxt)
    counts = bpc.bit_count_neighbors(up, down, op, side)

    if gidx is None:
        gidx, lane = bpc.site_groups(0, op.shape)
    draws = bpc.lane_draws(gidx, lane, offset, k0, k1)
    return target ^ bpc.flip_word_from_classes(target, counts, draws, thr)


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


def bitplane_sweeps_resident(black_words, white_words, inv_temp, *,
                             n_sweeps: int, seed=0, start_offset=0,
                             interpret: bool = False, thresholds=None):
    """``n_sweeps`` bitplane full sweeps in ONE dispatch, planes resident.

    Bit-exact vs ``core.bitplane.run_sweeps_bitplane`` at the same
    ``start_offset``; advances all 32 replica chains.
    """
    assert n_sweeps >= 1, n_sweeps
    n, w = black_words.shape
    assert w % 4 == 0, "bitplane planes need a multiple-of-4 width"
    if thresholds is None:
        thresholds = bpc.ms.acceptance_thresholds(inv_temp)
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([jnp.asarray(k0, jnp.uint32),
                       jnp.asarray(k1, jnp.uint32),
                       jnp.asarray(start_offset, jnp.uint32)])

    plane = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, n_sweeps=n_sweeps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (k0, k1, offset)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # acceptance thresholds
            plane,                                   # black bits (resident)
            plane,                                   # white bits (resident)
        ],
        out_specs=(plane, plane),
        out_shape=(jax.ShapeDtypeStruct(black_words.shape,
                                        black_words.dtype),
                   jax.ShapeDtypeStruct(white_words.shape,
                                        white_words.dtype)),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("bitplane", "resident"),
    )(seeds, thresholds, black_words, white_words)
