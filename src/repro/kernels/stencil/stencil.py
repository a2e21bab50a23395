"""Pallas TPU kernel: fused checkerboard Metropolis stencil update.

TPU adaptation of the paper's basic CUDA kernel (Fig. 2): instead of one
thread per spin, the grid iterates over row blocks of the compact color
plane; each step stages the target block and the THREE relevant source
blocks (row-block i-1, i, i+1 -- periodic wrap via a modulo index_map)
into VMEM and performs the whole neighbor-sum + accept + flip on the VPU.
Blocks span the full row width so the side-neighbor wrap is a VMEM-local
roll; row blocks are even-height so checkerboard parity is block-uniform.

With in-kernel Philox (``uniforms=None``) this fuses what the paper's
basic implementation does in two passes (cuRAND host-API array population,
then update) into one -- DESIGN.md S6.2.
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

DEFAULT_BLOCK_ROWS = 256


def _side(op, rows_parity, is_black):
    # column wrap as slice-concat (pad+slice form, H1.4): fusible
    # producers instead of jnp.roll's gather lowering
    plus = jnp.concatenate([op[:, 1:], op[:, :1]], axis=1)
    minus = jnp.concatenate([op[:, -1:], op[:, :-1]], axis=1)
    if is_black:
        return jnp.where(rows_parity == 1, plus, minus)
    return jnp.where(rows_parity == 1, minus, plus)


def _kernel(beta_ref, seeds_ref, target_ref, op_m1_ref, op_0_ref, op_p1_ref,
            out_ref, *, is_black: bool, block_rows: int, use_philox: bool,
            uniforms_ref=None):
    inv_temp = beta_ref[0]
    # neighbour sums in int32: Mosaic has no int8 vector arithmetic.
    # |sum| <= 4, so the values, the float32 accept and the flips are
    # those of the int8 oracle (H1.5)
    op = op_0_ref[...].astype(jnp.int32)
    up_row = op_m1_ref[...].astype(jnp.int32)[-1:, :]
    down_row = op_p1_ref[...].astype(jnp.int32)[:1, :]
    up = jnp.concatenate([up_row, op[:-1, :]], axis=0)
    down = jnp.concatenate([op[1:, :], down_row], axis=0)
    parity = (jax.lax.broadcasted_iota(jnp.int32, op.shape, 0)
              % 2)  # block height is even => local parity == global parity
    nn = up + down + op + _side(op, parity, is_black)

    t = target_ref[...].astype(jnp.int32)
    if use_philox:
        k0 = seeds_ref[0]
        k1 = seeds_ref[1]
        offset = seeds_ref[2]
        i = pl.program_id(0)
        h = op.shape[1]
        rows = i * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, op.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, op.shape, 1)
        gidx = (rows * h + cols).astype(jnp.uint32)
        zero = jnp.zeros_like(gidx)
        bits = crng.philox4x32(offset, zero, gidx, zero, k0, k1)[0]
        u = crng.u32_to_uniform(bits)
    else:
        u = uniforms_ref[...]
    acc = jnp.exp(-2.0 * inv_temp * nn.astype(jnp.float32)
                  * t.astype(jnp.float32))
    out_ref[...] = jnp.where(u < acc, -t, t).astype(out_ref.dtype)


def stencil_update(target, op_plane, inv_temp, *, is_black: bool,
                   uniforms=None, seed: int = 0, offset=0,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """One color half-sweep. If ``uniforms`` is None, draws Philox in-kernel.

    The Philox stream is keyed on the *global* (row, col) index, matching
    ``repro.core.metropolis.update_color_philox`` bit-for-bit.
    """
    n, h = target.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0 and block_rows % 2 == 0
    nb = n // block_rows
    use_philox = uniforms is None

    beta = jnp.array([inv_temp], jnp.float32)
    # seed may be a python int or a traced uint32 scalar (ensemble vmap);
    # both Philox key lanes ride to SMEM so 64-bit seeds match the
    # basic_philox oracle bit-for-bit
    k0, k1 = crng.seed_keys(seed)
    seeds = jnp.stack([k0, k1, jnp.asarray(offset, jnp.uint32)])

    row_spec = pl.BlockSpec((block_rows, h), lambda i: (i, 0))
    specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),            # beta
        pl.BlockSpec(memory_space=pltpu.SMEM),            # seed/offset
        row_spec,                                          # target
        pl.BlockSpec((block_rows, h), lambda i: ((i - 1) % nb, 0)),
        row_spec,
        pl.BlockSpec((block_rows, h), lambda i: ((i + 1) % nb, 0)),
    ]
    args = [beta, seeds, target, op_plane, op_plane, op_plane]
    kern = functools.partial(_kernel, is_black=is_black,
                             block_rows=block_rows, use_philox=use_philox)
    if not use_philox:
        def kern_u(b, s, t, m1, c0, p1, u, o):
            _kernel(b, s, t, m1, c0, p1, o, is_black=is_black,
                    block_rows=block_rows, use_philox=False, uniforms_ref=u)
        kern = kern_u
        specs.append(row_spec)
        args.append(uniforms)

    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(target.shape, target.dtype),
        interpret=interpret,
        compiler_params=vmem.compiler_params(),
        name=kernel_name("stencil", "stream"),
    )(*args)
