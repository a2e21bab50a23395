"""shard_map driver for the sharded resident tier (DESIGN.md S15).

``make_resident_step(mesh, plan)`` builds the distributed analogue of
the S9 resident dispatch: one jitted call advances ``n_sweeps`` full
sweeps, but instead of exchanging 1-wide halos every half-sweep
(``core.distributed``), each shard

1. **gathers** a width ``h = 2k`` halo ring in two ring-shift stages
   -- columns first, then rows on the column-extended plane, so the
   row strips carry the corner cells (a diagonal neighbor's data
   arrives in two hops, never needing a diagonal ppermute);
2. **sweeps** ``k`` full sweeps in ONE per-shard Pallas kernel
   (``dist.kernels``) on the extended plane, VMEM-resident, with
   Philox draws keyed on precomputed global-index planes;
3. **slices** the owned interior ``[h:-h, h:-h]`` back out -- exact,
   because edge garbage creeps inward one ring per half-sweep and
   ``2k`` half-sweeps contaminate exactly the ``h`` halo rings.

Blocks repeat inside a ``fori_loop`` (one exchange per ``k`` sweeps);
a static remainder block of ``n_sweeps % k`` sweeps reuses the same
halo width (its contamination depth ``2(n_sweeps % k) < h`` stays
inside the ring).

Stream invariance: the index planes hold TRUE global positions
(modular arithmetic across the periodic wrap), and offsets advance by
``core.rng.half_sweep_offset`` from a half-sweep-unit ``start``
argument -- the same counter layout as every other tier -- so the
trajectory is bit-identical to the single-device resident kernels on
any mesh, and checkpoints restore across mesh shapes
(tests/test_dist.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distributed import ring_shift
from repro.kernels import resident

from . import kernels as dk
from .planner import ShardPlan


def _multi_index(axes):
    """Linear device index over a product of mesh axes (msb first)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _extend(x, h: int, row_axes, col_axes):
    """Halo-extend one shard plane by ``h`` rings: two-stage ring-shift
    gather (columns, then rows on the column-extended plane) so the
    row strips carry the corners."""
    left = ring_shift(x[:, -h:], col_axes, +1)
    right = ring_shift(x[:, :h], col_axes, -1)
    xw = jnp.concatenate([left, x, right], axis=1)
    top = ring_shift(xw[-h:, :], row_axes, +1)
    bottom = ring_shift(xw[:h, :], row_axes, -1)
    return jnp.concatenate([top, xw, bottom], axis=0)


def make_resident_step(mesh, plan: ShardPlan, *, seed: int = 0,
                       n_sweeps: int = 1, row_axes=None, col_axes=None,
                       interpret=None):
    """Build the jitted sharded-resident sweep for ``mesh``/``plan``.

    Returns ``(step, sharding)`` where
    ``step(black, white, inv_temp, start)`` advances ``n_sweeps`` full
    sweeps from half-sweep offset ``start`` (uint32 -- pass
    ``2 * step_count``, the S9 resident ``start_offset`` convention)
    and the plane buffers are donated.  ``interpret=None`` resolves to
    the engines' convention (interpreter off only on real TPUs).
    """
    names = list(mesh.axis_names)
    row_axes = tuple(row_axes if row_axes is not None else names[:-1])
    col_axes = tuple(col_axes if col_axes is not None else names[-1:])
    rows_devs = 1
    for a in row_axes:
        rows_devs *= mesh.shape[a]
    cols_devs = 1
    for a in col_axes:
        cols_devs *= mesh.shape[a]
    assert (rows_devs, cols_devs) == (plan.rows_devs, plan.cols_devs), (
        f"plan grid {plan.rows_devs}x{plan.cols_devs} != mesh grid "
        f"{rows_devs}x{cols_devs}")
    assert n_sweeps >= 1, n_sweeps
    if interpret is None:
        interpret = resident.interpret_mode()

    fam, h, k = plan.family, plan.halo, plan.k
    width = plan.width
    n_blocks, rem = divmod(n_sweeps, k)
    spec = P(row_axes, col_axes)

    def _ext_positions():
        """Global (row, col) int32 planes of the EXTENDED shard cells,
        modular across the periodic wrap."""
        r0 = _multi_index(row_axes) * plan.n_loc
        c0 = _multi_index(col_axes) * plan.w_loc
        rows = jnp.mod(
            r0 - h + jnp.arange(plan.n_loc + 2 * h, dtype=jnp.int32),
            plan.n)[:, None]
        cols = jnp.mod(
            c0 - h + jnp.arange(plan.w_loc + 2 * h, dtype=jnp.int32),
            width)[None, :]
        return rows, cols

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, P(), P()),
                       out_specs=(spec, spec), check_vma=False)
    def _sweeps(black, white, inv_temp, start):
        rows, cols = _ext_positions()
        if fam == "stencil":
            gidx = (rows * width + cols).astype(jnp.uint32)
            gidx = jnp.broadcast_to(gidx, (rows.shape[0], cols.shape[1]))

            def run_block(b, w, off, sweeps):
                bx, wx = (_extend(b, h, row_axes, col_axes),
                          _extend(w, h, row_axes, col_axes))
                bx, wx = dk.stencil_shard_sweeps(
                    bx, wx, inv_temp, gidx, n_sweeps=sweeps, seed=seed,
                    start_offset=off, interpret=interpret)
                return bx[h:-h, h:-h], wx[h:-h, h:-h]
        elif fam == "multispin":
            from repro.core import multispin as ms
            thresholds = ms.acceptance_thresholds(inv_temp)
            widx = (rows * width + cols).astype(jnp.uint32)
            widx = jnp.broadcast_to(widx, (rows.shape[0], cols.shape[1]))

            def run_block(b, w, off, sweeps):
                bx, wx = (_extend(b, h, row_axes, col_axes),
                          _extend(w, h, row_axes, col_axes))
                bx, wx = dk.multispin_shard_sweeps(
                    bx, wx, thresholds, widx, n_sweeps=sweeps,
                    seed=seed, start_offset=off, interpret=interpret)
                return bx[h:-h, h:-h], wx[h:-h, h:-h]
        else:  # bitplane
            from repro.core import multispin as ms
            thresholds = ms.acceptance_thresholds(inv_temp)
            shape = (rows.shape[0], cols.shape[1])
            g = jnp.broadcast_to(
                (rows * (width // 4) + cols // 4).astype(jnp.uint32),
                shape)
            lane = jnp.broadcast_to((cols % 4).astype(jnp.uint32),
                                    shape)

            def run_block(b, w, off, sweeps):
                bx, wx = (_extend(b, h, row_axes, col_axes),
                          _extend(w, h, row_axes, col_axes))
                bx, wx = dk.bitplane_shard_sweeps(
                    bx, wx, thresholds, g, lane, n_sweeps=sweeps,
                    seed=seed, start_offset=off, interpret=interpret)
                return bx[h:-h, h:-h], wx[h:-h, h:-h]

        def body(j, carry):
            b, w = carry
            off = start + jnp.uint32(2 * k) * j.astype(jnp.uint32)
            return run_block(b, w, off, k)

        b, w = black, white
        if n_blocks:
            b, w = jax.lax.fori_loop(0, n_blocks, body, (b, w))
        if rem:
            b, w = run_block(b, w,
                             start + jnp.uint32(2 * k * n_blocks), rem)
        return b, w

    return (jax.jit(_sweeps, donate_argnums=(0, 1)),
            jax.sharding.NamedSharding(mesh, spec))
