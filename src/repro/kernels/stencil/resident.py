"""Pallas TPU kernel: VMEM-resident k-full-sweep stencil update (S9).

The per-half-sweep kernel (``stencil.py``) round-trips both compact
color planes through HBM twice per sweep.  This kernel stages BOTH
planes into VMEM once (no grid: one program owns the whole lattice --
the planner in ``kernels/resident.py`` guarantees the working set
fits), runs ``n_sweeps`` full sweeps -- black then white half-sweeps --
in an in-kernel ``lax.fori_loop``, and writes both planes back once.
Philox offsets advance in-kernel per (sweep, color) via
``core.rng.half_sweep_offset``, the same counter layout every host-side
sweep loop uses, so the output is bit-for-bit ``n_sweeps`` applications
of the per-half-sweep oracle (``basic_philox`` -- tested in
tests/test_resident.py) and checkpoints/restarts keep their stream.

Neighbor shifts are slice-concat (pad+slice form, H1.4); the int8
planes widen to int32 for the neighbor sums (Mosaic has no int8 vector
arithmetic; |sum| <= 4, so the values are ``core.metropolis``'s int8
sums, H1.5).  Plane inputs are aliased to the
outputs (``input_output_aliases``), so together with the donated jit
wrappers (H1.8) the planes never hold two HBM copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng as crng
from repro.kernels import resident as vmem
from repro.kernels.names import kernel_name


def _half_sweep(target, op, inv_temp, is_black: bool, k0, k1, offset,
                gidx=None):
    """One color half-sweep on whole VMEM-resident planes.

    Identical math (and float op order) to ``stencil.py``'s blocked
    kernel / ``core.metropolis.update_color_philox``: the same neighbor
    sums (widened to int32), global (row, col) Philox keying,
    ``exp(-2 beta nn s)`` accept.

    ``gidx`` overrides the Philox site keying with a precomputed uint32
    global-index plane -- the sharded resident tier (``repro.dist``)
    passes the TRUE global positions of its halo-extended shard, so the
    draws match this kernel's own iota keying on the full lattice.
    """
    op = op.astype(jnp.int32)
    t = target.astype(jnp.int32)
    up = jnp.concatenate([op[-1:, :], op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], op[:1, :]], axis=0)
    plus = jnp.concatenate([op[:, 1:], op[:, :1]], axis=1)
    minus = jnp.concatenate([op[:, -1:], op[:, :-1]], axis=1)
    parity = jax.lax.broadcasted_iota(jnp.int32, op.shape, 0) % 2
    if is_black:
        side = jnp.where(parity == 1, plus, minus)
    else:
        side = jnp.where(parity == 1, minus, plus)
    nn = up + down + op + side

    if gidx is None:
        h = op.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, op.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, op.shape, 1)
        gidx = (rows * h + cols).astype(jnp.uint32)
    zero = jnp.zeros_like(gidx)
    bits = crng.philox4x32(offset, zero, gidx, zero, k0, k1)[0]
    u = crng.u32_to_uniform(bits)
    acc = jnp.exp(-2.0 * inv_temp * nn.astype(jnp.float32)
                  * t.astype(jnp.float32))
    return jnp.where(u < acc, -t, t).astype(target.dtype)


def _kernel(beta_ref, seeds_ref, black_ref, white_ref, black_out,
            white_out, *, n_sweeps: int):
    inv_temp = beta_ref[0]
    k0 = seeds_ref[0]
    k1 = seeds_ref[1]
    start = seeds_ref[2]

    def body(i, carry):
        b, w = carry
        b = _half_sweep(b, w, inv_temp, True, k0, k1,
                        crng.half_sweep_offset(start, i, 0))
        w = _half_sweep(w, b, inv_temp, False, k0, k1,
                        crng.half_sweep_offset(start, i, 1))
        return (b, w)

    b, w = jax.lax.fori_loop(0, n_sweeps, body,
                             (black_ref[...], white_ref[...]))
    black_out[...] = b
    white_out[...] = w


def stencil_sweeps_resident(black, white, inv_temp, *, n_sweeps: int,
                            seed=0, start_offset=0,
                            interpret: bool = False):
    """``n_sweeps`` full sweeps in ONE dispatch, planes VMEM-resident.

    Bit-exact vs ``n_sweeps`` iterations of the per-half-sweep oracle
    (``core.metropolis.run_sweeps_philox``) at the same
    ``start_offset``; ``seed`` may be a python int (full 64-bit key) or
    a traced uint32 (ensemble vmap), exactly like the blocked kernel.
    """
    assert n_sweeps >= 1, n_sweeps
    beta = jnp.array([inv_temp], jnp.float32)
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1, jnp.asarray(start_offset, jnp.uint32)])

    plane = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, n_sweeps=n_sweeps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # beta
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (k0, k1, offset)
            plane,                                   # black (resident)
            plane,                                   # white (resident)
        ],
        out_specs=(plane, plane),
        out_shape=(jax.ShapeDtypeStruct(black.shape, black.dtype),
                   jax.ShapeDtypeStruct(white.shape, white.dtype)),
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("stencil", "resident"),
    )(beta, seeds, black, white)
