"""Per-shard VMEM-resident k-sweep Pallas kernels (DESIGN.md S15).

Global-index-keyed variants of the S9 resident kernels
(``kernels/{stencil,multispin,bitplane}/resident.py``): the half-sweep
math is IMPORTED from those modules (same fusion structure, same float
op order, bit-exactness by construction) -- the only difference is
that Philox draws are keyed on precomputed uint32 *global* index
planes instead of in-kernel iota, because the planes these kernels see
are halo-EXTENDED shards whose cells live at arbitrary (and, across
the periodic wrap, non-contiguous) global positions.

Each kernel stages the extended planes plus the index plane(s) into
VMEM once, runs ``n_sweeps`` full sweeps in an in-kernel
``lax.fori_loop`` with offsets advanced per (sweep, color) by
``core.rng.half_sweep_offset``, and writes the planes back once
(extended inputs aliased to the outputs).  Every half-sweep updates
the WHOLE extended plane -- no masks: the wraparound taps at the
extended edge read garbage, but garbage propagates inward at exactly
one ring per half-sweep, so after ``2k`` half-sweeps only the
``h = 2k`` halo rings are contaminated and the caller's interior
slice ``[h:-h, h:-h]`` is exact (the S15 double-halo argument).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng as crng
from repro.kernels import resident as vmem
from repro.kernels.bitplane import resident as bp_res
from repro.kernels.multispin import resident as ms_res
from repro.kernels.names import kernel_name
from repro.kernels.stencil import resident as st_res

_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _loop(half_sweep, seeds_ref, n_sweeps, black_ref, white_ref,
          black_out, white_out):
    """The shared (sweep, color) offset loop over a half-sweep fn."""
    start = seeds_ref[2]

    def body(i, carry):
        b, w = carry
        b = half_sweep(b, w, True, crng.half_sweep_offset(start, i, 0))
        w = half_sweep(w, b, False, crng.half_sweep_offset(start, i, 1))
        return (b, w)

    b, w = jax.lax.fori_loop(0, n_sweeps, body,
                             (black_ref[...], white_ref[...]))
    black_out[...] = b
    white_out[...] = w


def _stencil_kernel(beta_ref, seeds_ref, gidx_ref, black_ref, white_ref,
                    black_out, white_out, *, n_sweeps: int):
    inv_temp = beta_ref[0]
    k0, k1 = seeds_ref[0], seeds_ref[1]
    gidx = gidx_ref[...]
    _loop(lambda t, op, is_b, off: st_res._half_sweep(
              t, op, inv_temp, is_b, k0, k1, off, gidx=gidx),
          seeds_ref, n_sweeps, black_ref, white_ref, black_out,
          white_out)


def stencil_shard_sweeps(black, white, inv_temp, gidx, *,
                         n_sweeps: int, seed, start_offset,
                         interpret: bool = False):
    """``n_sweeps`` sweeps of one halo-extended int8 shard, resident."""
    assert n_sweeps >= 1, n_sweeps
    beta = jnp.array([inv_temp], jnp.float32)
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1,
                       jnp.asarray(start_offset, jnp.uint32)])
    return pl.pallas_call(
        functools.partial(_stencil_kernel, n_sweeps=n_sweeps),
        in_specs=[_SMEM, _SMEM, _VMEM, _VMEM, _VMEM],
        out_specs=(_VMEM, _VMEM),
        out_shape=(jax.ShapeDtypeStruct(black.shape, black.dtype),
                   jax.ShapeDtypeStruct(white.shape, white.dtype)),
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("stencil", "shard_resident"),
    )(beta, seeds, gidx, black, white)


def _multispin_kernel(seeds_ref, thr_ref, widx_ref, black_ref,
                      white_ref, black_out, white_out, *,
                      n_sweeps: int):
    k0, k1 = seeds_ref[0], seeds_ref[1]
    thr = [thr_ref[c] for c in range(10)]  # SMEM scalar reads
    widx = widx_ref[...]
    _loop(lambda t, op, is_b, off: ms_res._half_sweep(
              t, op, is_b, thr, k0, k1, off, widx=widx),
          seeds_ref, n_sweeps, black_ref, white_ref, black_out,
          white_out)


def multispin_shard_sweeps(black, white, thresholds, widx, *,
                           n_sweeps: int, seed, start_offset,
                           interpret: bool = False):
    """``n_sweeps`` sweeps of one halo-extended packed-word shard."""
    assert n_sweeps >= 1, n_sweeps
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1,
                       jnp.asarray(start_offset, jnp.uint32)])
    return pl.pallas_call(
        functools.partial(_multispin_kernel, n_sweeps=n_sweeps),
        in_specs=[_SMEM, _SMEM, _VMEM, _VMEM, _VMEM],
        out_specs=(_VMEM, _VMEM),
        out_shape=(jax.ShapeDtypeStruct(black.shape, black.dtype),
                   jax.ShapeDtypeStruct(white.shape, white.dtype)),
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("multispin", "shard_resident"),
    )(seeds, thresholds, widx, black, white)


def _bitplane_kernel(seeds_ref, thr_ref, gidx_ref, lane_ref, black_ref,
                     white_ref, black_out, white_out, *, n_sweeps: int):
    k0, k1 = seeds_ref[0], seeds_ref[1]
    thr = [thr_ref[c] for c in range(10)]  # SMEM scalar reads
    gidx = gidx_ref[...]
    lane = lane_ref[...]
    _loop(lambda t, op, is_b, off: bp_res._half_sweep(
              t, op, is_b, thr, k0, k1, off, gidx=gidx, lane=lane),
          seeds_ref, n_sweeps, black_ref, white_ref, black_out,
          white_out)


def bitplane_shard_sweeps(black, white, thresholds, gidx, lane, *,
                          n_sweeps: int, seed, start_offset,
                          interpret: bool = False):
    """``n_sweeps`` sweeps of one halo-extended 32-replica bit shard."""
    assert n_sweeps >= 1, n_sweeps
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([jnp.asarray(k0, jnp.uint32),
                       jnp.asarray(k1, jnp.uint32),
                       jnp.asarray(start_offset, jnp.uint32)])
    return pl.pallas_call(
        functools.partial(_bitplane_kernel, n_sweeps=n_sweeps),
        in_specs=[_SMEM, _SMEM, _VMEM, _VMEM, _VMEM, _VMEM],
        out_specs=(_VMEM, _VMEM),
        out_shape=(jax.ShapeDtypeStruct(black.shape, black.dtype),
                   jax.ShapeDtypeStruct(white.shape, white.dtype)),
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("bitplane", "shard_resident"),
    )(seeds, thresholds, gidx, lane, black, white)
