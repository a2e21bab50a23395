"""Pallas TPU kernel: FUSED tensor-core (MXU) Metropolis update.

The paper's tensor-core implementation (S3.2) runs three separate passes
per color -- batched GEMMs (cublasHgemmBatched), a boundary kernel, and an
update kernel -- and loses to the stencil because of the extra HBM
round-trips.  This kernel is the beyond-paper fix (DESIGN.md S6.1): one
grid step stages a 128x128 block pair of the target planes plus the six
neighbor source blocks into VMEM, runs both banded GEMMs on the MXU
(bf16 in, f32 accumulate -- the MXU-native layout), applies the boundary
corrections and the Metropolis accept, and writes the flipped spins.  One
HBM round-trip instead of three.

Block edges use modulo index_maps for periodic wrap.  Spins are stored
bf16 (the paper's half-precision choice); sums accumulate in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng as crng
from repro.core.tensorcore import make_kernel_matrix
from repro.kernels.names import kernel_name

DEFAULT_BLOCK = 128


def _philox_uniform_pair(seed, offset, gidx):
    """Two decorrelated uniforms per plane position (lanes 0/1)."""
    zero = jnp.zeros_like(gidx)
    r = crng.philox4x32(offset, zero, gidx, zero, seed, jnp.uint32(0))
    return crng.u32_to_uniform(r[0]), crng.u32_to_uniform(r[1])


def _accept(t, nn, u, inv_temp):
    tf = t.astype(jnp.float32)
    acc = jnp.exp(-2.0 * inv_temp * nn * tf)
    return jnp.where(u < acc, -tf, tf).astype(t.dtype)


def _kernel(beta_ref, seeds_ref, k_ref, t1_ref, t2_ref, a_c_ref, a_side_ref,
            a_vert_ref, b_c_ref, b_vert_ref, b_side_ref, out1_ref, out2_ref,
            *, is_black: bool, block: int, plane_w: int):
    inv_temp = beta_ref[0]
    k = k_ref[...]
    kt = k.T
    a = a_c_ref[...]
    b = b_c_ref[...]

    dot = functools.partial(jax.lax.dot,
                            preferred_element_type=jnp.float32)
    if is_black:
        # nn(s00) = s01 K + K^T s10 ; nn(s11) = s10 K^T + K s01
        nn1 = dot(a, k) + dot(kt, b)
        nn2 = dot(b, kt) + dot(k, a)
    else:
        # nn(s10) = s11 K + K s00 ; nn(s01) = s00 K^T + K^T s11
        nn1 = dot(a, k) + dot(k, b)
        nn2 = dot(b, kt) + dot(kt, a)

    rows = jax.lax.broadcasted_iota(jnp.int32, nn1.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, nn1.shape, 1)
    first_c = (cols == 0).astype(jnp.float32)
    last_c = (cols == block - 1).astype(jnp.float32)
    first_r = (rows == 0).astype(jnp.float32)
    last_r = (rows == block - 1).astype(jnp.float32)

    a_side = a_side_ref[...].astype(jnp.float32)   # block (i, j-1)
    a_vert = a_vert_ref[...].astype(jnp.float32)   # (i+1, j) black / (i-1, j) white
    b_vert = b_vert_ref[...].astype(jnp.float32)   # (i-1, j) black / (i+1, j) white
    b_side = b_side_ref[...].astype(jnp.float32)   # block (i, j+1)

    if is_black:
        nn1 = nn1 + first_c * a_side[:, -1:] + first_r * b_vert[-1:, :]
        nn2 = nn2 + last_c * b_side[:, :1] + last_r * a_vert[:1, :]
    else:
        nn1 = nn1 + first_c * a_side[:, -1:] + last_r * b_vert[:1, :]
        nn2 = nn2 + last_c * b_side[:, :1] + first_r * a_vert[-1:, :]

    seed = seeds_ref[0]
    offset = seeds_ref[1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    gi = i * block + rows
    gj = j * block + cols
    gidx = (gi * plane_w + gj).astype(jnp.uint32)
    u1, u2 = _philox_uniform_pair(seed, offset, gidx)

    out1_ref[...] = _accept(t1_ref[...], nn1, u1, inv_temp)
    out2_ref[...] = _accept(t2_ref[...], nn2, u2, inv_temp)


def tensorcore_update(planes: dict, color: str, inv_temp, *, seed: int = 0,
                      offset=0, block: int = DEFAULT_BLOCK,
                      interpret: bool = False) -> dict:
    """Fused MXU half-sweep for one color. planes: {'00','01','10','11'} bf16."""
    is_black = color == "black"
    t1k, t2k = ("00", "11") if is_black else ("10", "01")
    ak, bk = ("01", "10") if is_black else ("11", "00")
    t1, t2, a, b = planes[t1k], planes[t2k], planes[ak], planes[bk]
    h, w = t1.shape
    assert h % block == 0 and w % block == 0
    nbi, nbj = h // block, w // block

    beta = jnp.array([inv_temp], jnp.float32)
    seeds = jnp.array([seed & 0xFFFFFFFF, offset], jnp.uint32)
    kmat = make_kernel_matrix(block)

    c = pl.BlockSpec((block, block), lambda i, j: (i, j))
    left = pl.BlockSpec((block, block), lambda i, j: (i, (j - 1) % nbj))
    right = pl.BlockSpec((block, block), lambda i, j: (i, (j + 1) % nbj))
    down = pl.BlockSpec((block, block), lambda i, j: ((i + 1) % nbi, j))
    up = pl.BlockSpec((block, block), lambda i, j: ((i - 1) % nbi, j))
    a_vert = down if is_black else up
    b_vert = up if is_black else down

    new1, new2 = pl.pallas_call(
        functools.partial(_kernel, is_black=is_black, block=block,
                          plane_w=w),
        grid=(nbi, nbj),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # beta
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seed/offset
            pl.BlockSpec((block, block), lambda i, j: (0, 0)),  # K
            c, c,                                    # targets
            c, left, a_vert,                         # a plane blocks
            c, b_vert, right,                        # b plane blocks
        ],
        out_specs=(c, c),
        out_shape=(jax.ShapeDtypeStruct(t1.shape, t1.dtype),
                   jax.ShapeDtypeStruct(t2.shape, t2.dtype)),
        interpret=interpret,
        name=kernel_name("tensorcore", "stream"),
    )(beta, seeds, kmat, t1, t2, a, a, a, b, b, b)

    out = dict(planes)
    out[t1k], out[t2k] = new1, new2
    return out
