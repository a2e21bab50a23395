"""Shard-aware resident planner: fit, halo width, and k per shard.

Extends the single-device VMEM planner (``kernels/resident.py``) to
pencil-sharded lattices.  The decision is per *shard*: the kernel's
working set is the EXTENDED plane -- the ``(n_loc, w_loc)`` owned cells
plus a ``h = 2k`` halo ring on every side -- so both the VMEM budget
and the halo-feasibility constraints depend on the device grid, not
just the lattice.

Constraints (DESIGN.md S15 decision table):

* **halo fit**: ``h <= min(n_loc, w_loc)`` -- the ring-shift gather
  takes the outermost ``h`` rows/columns of each neighbor shard, so a
  halo wider than the shard itself would need multi-hop gathers the
  driver does not implement (and that would be slower than the
  per-half-sweep fallback anyway);
* **VMEM fit**: the extended working set -- padded extended cells
  times the family's S9 resident bytes per cell, plus the uint32
  global-index planes the kernel needs for Philox keying -- must fit
  the same ``VMEM_LIMIT_BYTES`` the single-device planner uses (and
  every kernel is compiled with);
* **overlap cap**: the extended area may be at most
  :data:`MAX_OVERLAP` times the owned area.  The halo cells are
  *redundantly* swept every half-sweep (that is the double-halo
  trade: compute for communication), so past ~2x the redundant work
  erases the exchange savings;
* **parity**: per-shard row counts must be even (checkerboard parity
  uniform across shards -- same rule as ``core.distributed``); the
  halo ``h = 2k`` is always even, so the extended plane's first row
  keeps global parity 0 and the kernels' local iota parity is exact.

``plan_shard_resident`` picks the largest feasible ``k`` up to
``k_cap`` and returns ``None`` when no ``k >= 1`` fits -- the caller
(``api.session._ShardedRunner``) then demotes to the per-half-sweep
distributed tier, which is bit-exact by the shared global-position
Philox keying.  A (family, lattice) demoted at runtime by
``resilience.degrade`` (e.g. a RESOURCE_EXHAUSTED launch) never fits
again this process, exactly like the single-device planner.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.kernels import resident
from repro.resilience import degrade

#: default cap on sweeps-per-exchange: past this the redundant halo
#: compute and the h^2 VMEM growth beat the exchange savings
K_CAP: int = 4

#: max extended-area / owned-area ratio before the redundant halo
#: sweep work disqualifies a k (see module docstring)
MAX_OVERLAP: float = 2.0

#: family -> uint32 index planes the kernel needs for global Philox
#: keying: gidx (stencil), widx (multispin), group + lane (bitplane).
#: Cell = one element of the compact color plane: an int8 site
#: (stencil), a uint32 8-spin word (multispin, m/16 per row), or a
#: uint32 32-replica word (bitplane, m/2 per row).
_INDEX_PLANES = {"stencil": 1, "multispin": 1, "bitplane": 2}


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """A positive fit: this (family, lattice, grid) runs the sharded
    resident tier with ``k`` sweeps per halo exchange."""

    family: str
    n: int                  # global plane rows
    m: int                  # global lattice columns
    rows_devs: int          # device-grid rows
    cols_devs: int          # device-grid columns
    n_loc: int              # owned plane rows per shard
    w_loc: int              # owned plane cells per shard row
    k: int                  # full sweeps per halo exchange
    halo: int               # halo ring width = 2k (always even)
    working_set_bytes: int  # modeled per-shard VMEM peak
    budget_bytes: int

    @property
    def width(self) -> int:
        """Global plane cells per row (family packing units)."""
        return resident.plane_width(self.family, self.m)

    @property
    def cell_bytes(self) -> int:
        return resident._FAMILIES[self.family].cell_bytes

    def exchanges(self, n_sweeps: int) -> int:
        """Halo exchange events one dispatch of ``n_sweeps`` performs:
        one per full k-sweep block plus one for the remainder block."""
        return max(1, math.ceil(n_sweeps / self.k))

    @property
    def halo_bytes_per_exchange(self) -> int:
        """Bytes moved across the mesh per exchange event: per shard,
        both color planes each gather 2 column strips ``(n_loc, h)``
        and then 2 row strips ``(h, w_loc + 2h)`` (the row strips ride
        on the column-extended plane so they carry the corners);
        summed over every shard in the grid."""
        h = self.halo
        per_plane = 2 * self.n_loc * h + 2 * h * (self.w_loc + 2 * h)
        return (2 * per_plane * self.cell_bytes
                * self.rows_devs * self.cols_devs)


def shard_working_set_bytes(family: str, n_loc: int, w_loc: int,
                            halo: int) -> int:
    """Modeled per-shard VMEM peak of the extended-plane kernel.

    Same model as the single-device planner (the S9 resident bytes per
    padded cell in ``kernels/resident._FAMILIES``) applied to the
    extended plane, plus one uint32 global-index plane per index input
    the kernel takes (Philox keying, S15).
    """
    rows, width = n_loc + 2 * halo, w_loc + 2 * halo
    cells = resident.padded_cells(family, rows, width)
    idx_cells = resident.padded_cells(family, rows, width, tile_rows=8)
    return (cells * resident._FAMILIES[family].resident_bytes
            + 4 * _INDEX_PLANES[family] * idx_cells)


def plan_shard_resident(family: str, n: int, m: int, rows_devs: int,
                        cols_devs: int, *,
                        budget_bytes: Optional[int] = None,
                        k_cap: int = K_CAP,
                        max_overlap: Optional[float] = None
                        ) -> Optional[ShardPlan]:
    """Fit decision for one (family, lattice, device grid).

    Returns the :class:`ShardPlan` with the largest feasible
    ``k <= k_cap``, or ``None`` when even ``k = 1`` violates a
    constraint -- the caller then runs the per-half-sweep distributed
    tier (bit-exact fallback).  ``max_overlap`` overrides
    :data:`MAX_OVERLAP` (tests pin k on small shards with it; the
    driver is exact at ANY feasible k, the cap is pure perf policy).
    """
    if family not in _INDEX_PLANES:
        raise ValueError(f"unknown resident family {family!r}; "
                         f"known: {sorted(_INDEX_PLANES)}")
    budget = (resident.VMEM_LIMIT_BYTES if budget_bytes is None
              else budget_bytes)
    overlap = MAX_OVERLAP if max_overlap is None else max_overlap
    width = resident.plane_width(family, m)
    if degrade.demotion_reason(family, n, m) is not None:
        return None
    if n % rows_devs or width % cols_devs:
        return None
    n_loc, w_loc = n // rows_devs, width // cols_devs
    if n_loc % 2:
        return None
    for k in range(max(1, k_cap), 0, -1):
        h = 2 * k
        if h > min(n_loc, w_loc):
            continue
        ext = (n_loc + 2 * h) * (w_loc + 2 * h)
        if ext > overlap * n_loc * w_loc:
            continue
        ws = shard_working_set_bytes(family, n_loc, w_loc, h)
        if ws > budget:
            continue
        return ShardPlan(family=family, n=n, m=m, rows_devs=rows_devs,
                         cols_devs=cols_devs, n_loc=n_loc, w_loc=w_loc,
                         k=k, halo=h, working_set_bytes=ws,
                         budget_bytes=budget)
    return None


def shard_decision_attrs(family: str, n: int, m: int, rows_devs: int,
                         cols_devs: int, *,
                         budget_bytes: Optional[int] = None,
                         k_cap: int = K_CAP) -> dict:
    """The shard planner's decision as one flat JSON-scalar dict --
    the single rendering shared by ``--dry-run`` (``describe``), the
    sharded dispatch span attributes, and tests, mirroring the
    single-device ``kernels.resident.decision_attrs`` contract."""
    budget = (resident.VMEM_LIMIT_BYTES if budget_bytes is None
              else budget_bytes)
    plan = plan_shard_resident(family, n, m, rows_devs, cols_devs,
                               budget_bytes=budget, k_cap=k_cap)
    attrs = {"family": family, "grid": f"{rows_devs}x{cols_devs}",
             "sharded_resident": plan is not None,
             "budget_bytes": budget}
    if plan is not None:
        attrs.update(halo_k=plan.k, halo_width=plan.halo,
                     n_loc=plan.n_loc, w_loc=plan.w_loc,
                     working_set_bytes=plan.working_set_bytes,
                     halo_bytes_per_exchange=plan.halo_bytes_per_exchange)
        return attrs
    demoted = degrade.demotion_reason(family, n, m)
    if demoted is not None:
        attrs["demoted"] = True
        attrs["reason"] = (f"demoted to per-half-sweep distributed "
                           f"tier: {demoted}")
    elif n % rows_devs or resident.plane_width(family, m) % cols_devs \
            or (n // rows_devs) % 2:
        attrs["reason"] = ("lattice does not tile the device grid "
                           "evenly: per-half-sweep distributed tier")
    else:
        attrs["reason"] = ("no k satisfies halo/VMEM/overlap "
                           "constraints: per-half-sweep distributed "
                           "tier")
    return attrs
