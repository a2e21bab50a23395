"""3D Ising model -- the case the paper motivates in S2 ("the study of
spin systems in higher dimensions is by no means trivial" -- no analytical
solution; numerical simulation only; cubic-lattice Tc ~= 4.5115 J).

Same checkerboard idea, one more axis: color = (i+j+k) % 2, 6 neighbors.
Uses the H1.4 fused-stencil pattern (pad+slice shifts, mask select) so the
update stays a single fusion.  Distributed: slab over the leading axis
with ppermute halos (make_ising3d_step), same ring machinery as 2D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


T_CRITICAL_3D = 4.5115  # numerically known, J = 1


def neighbor_sums_3d(s):
    """6-neighbor sums with periodic wrap (single device)."""
    x = s.astype(jnp.int32)
    out = jnp.zeros_like(x)
    for axis in range(3):
        out = out + jnp.roll(x, 1, axis) + jnp.roll(x, -1, axis)
    return out


def _color_mask(shape, color):
    ii = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    kk = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    return ((ii + jj + kk) % 2) == color


def update_color_3d(full, uniforms, inv_temp, color: int):
    nn = neighbor_sums_3d(full)
    s = full.astype(jnp.int32)
    acc = jnp.exp(-2.0 * inv_temp * nn.astype(jnp.float32)
                  * s.astype(jnp.float32))
    flip = _color_mask(full.shape, color) & (uniforms < acc)
    return jnp.where(flip, -s, s).astype(full.dtype)


@functools.partial(jax.jit, static_argnames=("n_sweeps",))
def run_sweeps_3d(full, inv_temp, key, n_sweeps: int):
    def body(i, carry):
        f, k = carry
        k, k0, k1 = jax.random.split(k, 3)
        f = update_color_3d(f, jax.random.uniform(k0, f.shape), inv_temp, 0)
        f = update_color_3d(f, jax.random.uniform(k1, f.shape), inv_temp, 1)
        return (f, k)
    return jax.lax.fori_loop(0, n_sweeps, body, (full, key))


def magnetization_3d(full):
    return full.astype(jnp.float32).mean()


# ---------------------------------------------------------------------------
# distributed: slab over axis 0, ppermute halos (paper S4 in 3D)
# ---------------------------------------------------------------------------

def make_ising3d_step(mesh, *, n: int, seed: int = 0, n_sweeps: int = 1,
                      slab_axes=None):
    """Slab-decomposed 3D sweep over ``slab_axes`` (default: all mesh
    axes flattened into the leading lattice axis ring)."""
    from . import distributed as dist
    from . import rng as crng

    names = list(mesh.axis_names)
    slab_axes = tuple(slab_axes if slab_axes is not None else names)
    spec = P(slab_axes, None, None)

    def update(full, inv_temp, color, offset):
        top = dist.ring_shift(full[-1:], slab_axes, +1)
        bottom = dist.ring_shift(full[:1], slab_axes, -1)
        nl = full.shape[0]
        x = full.astype(jnp.int32)
        row_i = jax.lax.broadcasted_iota(jnp.int32, full.shape, 0)

        def shift0(v, d):
            padded = jnp.pad(v, ((1, 1), (0, 0), (0, 0)))
            return jax.lax.slice_in_dim(padded, 1 + d, 1 + d + nl, axis=0)

        nn = (jnp.where(row_i == 0, top.astype(jnp.int32), shift0(x, -1))
              + jnp.where(row_i == nl - 1, bottom.astype(jnp.int32),
                          shift0(x, 1)))
        for axis in (1, 2):
            nn = nn + jnp.roll(x, 1, axis) + jnp.roll(x, -1, axis)

        # global-position-keyed philox (grid independence, as in 2D)
        r0 = jnp.int32(0)
        for a in slab_axes:
            r0 = r0 * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        gi = (r0 * nl + row_i) * full.shape[1] * full.shape[2] \
            + jax.lax.broadcasted_iota(jnp.int32, full.shape, 1) \
            * full.shape[2] \
            + jax.lax.broadcasted_iota(jnp.int32, full.shape, 2)
        u = crng.uniforms(seed, gi.astype(jnp.uint32),
                          jnp.uint32(offset))[0]
        acc = jnp.exp(-2.0 * inv_temp * nn.astype(jnp.float32)
                      * x.astype(jnp.float32))
        ii = row_i + r0 * nl  # global parity along the sharded axis
        jj = jax.lax.broadcasted_iota(jnp.int32, full.shape, 1)
        kk = jax.lax.broadcasted_iota(jnp.int32, full.shape, 2)
        flip = (((ii + jj + kk) % 2) == color) & (u < acc)
        return jnp.where(flip, -x, x).astype(full.dtype)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, P(), P()),
                       out_specs=spec, check_vma=False)
    def sweeps(full, inv_temp, sweep0):
        def body(i, f):
            f = update(f, inv_temp, 0, crng.half_sweep_offset(sweep0, i, 0))
            f = update(f, inv_temp, 1, crng.half_sweep_offset(sweep0, i, 1))
            return f
        return jax.lax.fori_loop(0, n_sweeps, body, full)

    return jax.jit(sweeps), jax.sharding.NamedSharding(mesh, spec)
