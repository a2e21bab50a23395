"""VMEM planner of every Pallas sweep kernel (DESIGN.md S9).

The per-half-sweep kernels (``kernels/{stencil,multispin,bitplane}``)
re-read and re-write both compact color planes through HBM twice per
sweep, so a ``measure_every``-sized block of ``k`` sweeps costs ``2k``
HBM round-trips of the whole working set.  When both planes FIT in
per-core VMEM, the resident kernels (``resident.py`` in each family
directory) instead stage the planes into VMEM once, run all ``k`` sweeps
in an in-kernel ``lax.fori_loop`` (Philox offsets advanced in-kernel per
(sweep, color) -- ``core.rng.half_sweep_offset``), and write the planes
back once: HBM traffic drops from O(k) plane round-trips to O(1).

This module is the single place that sizes VMEM.  Every sweep kernel is
compiled with :data:`VMEM_LIMIT_BYTES` as Mosaic's scoped-VMEM limit
(:func:`compiler_params`), and both decisions below are checked against
that same limit:

* :func:`block_plan` -- the row-block height of the per-half-sweep
  kernels, from the plane's padded row bytes and its dtype tile;
* :func:`plan_resident` -- whether a lattice runs resident (``None``
  sends it to the per-half-sweep tier).  The engines
  (``core/engine.py``) compute the plan once at construction, so
  ``Simulation``, ``Ensemble`` and ``measure_scan`` pick the tier up
  with no caller changes.

Working-set model: Mosaic lays a plane out in (tile rows, 128 lanes)
tiles, so a kernel's scoped VMEM is its PADDED plane cells times a
per-family byte count.  The byte counts are what Mosaic allocated for
TPU v5e (the smallest ``vmem_limit_bytes`` each kernel compiles under,
rounded up):

==========  ===========  ==============  ===============
family      plane dtype  per-half-sweep  resident
==========  ===========  ==============  ===============
stencil     int8         57-59 -> 60     54-57 -> 60
multispin   uint32       90-92 -> 96     72-88 -> 96
bitplane    uint32       93-95 -> 96     80-81 -> 88
==========  ===========  ==============  ===============

(bytes per padded cell of one color plane; the per-half-sweep count is
per cell of ONE row block and includes the double-buffered target,
three source blocks and output).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.resilience import degrade
from repro.telemetry import TRACER

#: Mosaic scoped-VMEM limit of every sweep kernel, and the budget both
#: the block height and the resident fit are checked against.  TPU v5e
#: has 128 MiB of VMEM per core; 32 MiB holds a 32-row int8 stencil
#: block at 32768 columns and keeps resident compiles under a minute.
VMEM_LIMIT_BYTES: int = 32 * 1024 * 1024

#: modeled working set one per-half-sweep row block aims for: more
#: rows per block buy nothing once the VPU, not the grid step, bounds a
#: block, and Mosaic's compile time grows with the block
BLOCK_TARGET_BYTES: int = 8 * 1024 * 1024

_LANES = 128


@dataclasses.dataclass(frozen=True)
class _Family:
    cells_per_row: int    # lattice columns per plane cell
    cell_bytes: int       # 1 (int8 site) or 4 (uint32 word)
    lattice_step: int     # lattice side multiple the engine accepts
    blocked_bytes: int    # VMEM per padded cell of one row block
    resident_bytes: int   # VMEM per padded cell of one resident plane

    @property
    def tile_rows(self) -> int:
        """Rows of one Mosaic tile: 32 for int8, 8 for 32-bit words."""
        return 32 // self.cell_bytes


_FAMILIES: Dict[str, _Family] = {
    "stencil": _Family(2, 1, 2, 60, 60),       # int8 site planes
    "multispin": _Family(16, 4, 16, 96, 96),   # 8 sites per uint32 word
    "bitplane": _Family(2, 4, 8, 96, 88),      # 32 replicas per word
}


def _family(family: str) -> _Family:
    if family not in _FAMILIES:
        raise ValueError(f"unknown resident family {family!r}; "
                         f"known: {sorted(_FAMILIES)}")
    return _FAMILIES[family]


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when JAX's
    backend is not a TPU.  On a TPU every sweep kernel compiles with
    Mosaic; there is no interpreter fallback."""
    import jax
    return jax.default_backend() != "tpu"


def compiler_params():
    """Mosaic compiler parameters of every Pallas sweep kernel."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def plane_width(family: str, m: int) -> int:
    """Cells per row of one compact color plane of an m-column lattice."""
    return m // _family(family).cells_per_row


def padded_cells(family: str, rows: int, width: int,
                 tile_rows: Optional[int] = None) -> int:
    """Cells of a (rows, width) plane after Mosaic's tile padding
    (``tile_rows`` defaults to the family's plane dtype tile)."""
    tile = _family(family).tile_rows if tile_rows is None else tile_rows
    return -(-rows // tile) * tile * -(-width // _LANES) * _LANES


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Row-block height of the per-half-sweep kernel, and the limit it
    was sized against (what :func:`compiler_params` passes to Mosaic)."""

    block_rows: int
    working_set_bytes: int
    vmem_limit_bytes: int


def block_plan(family: str, n: int, m: int) -> BlockPlan:
    """Row-block height of the per-half-sweep kernel for an (n, m) lattice.

    Blocks span the full plane width, so the height is picked from the
    padded row bytes: the tallest block that divides the ``n`` plane rows,
    is a whole number of dtype tiles (or the whole plane) and stays within
    :data:`BLOCK_TARGET_BYTES`; a plane too wide for that gets one tile.
    Tile heights are even, so checkerboard parity is block-uniform.
    """
    f = _family(family)
    tile = f.tile_rows
    width = plane_width(family, m)
    row_bytes = padded_cells(family, tile, width) // tile * f.blocked_bytes
    heights = [r for r in range(tile, n, tile) if n % r == 0] + [n]
    fits = [r for r in heights if r * row_bytes <= BLOCK_TARGET_BYTES]
    rows = max(fits) if fits else heights[0]
    if rows % 2:
        raise ValueError(f"Pallas row-block engines need an even lattice "
                         f"height, got {n}")
    ws = padded_cells(family, rows, width) * f.blocked_bytes
    if ws > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{family} plane rows of {width} cells need {ws} B of VMEM "
            f"per {rows}-row block, over the {VMEM_LIMIT_BYTES} B limit")
    return BlockPlan(block_rows=rows, working_set_bytes=ws,
                     vmem_limit_bytes=VMEM_LIMIT_BYTES)


@dataclasses.dataclass(frozen=True)
class ResidentPlan:
    """A positive fit decision: this (family, lattice) runs resident."""

    family: str
    n: int
    m: int
    plane_bytes: int
    working_set_bytes: int
    budget_bytes: int


def plane_bytes(family: str, n: int, m: int) -> int:
    """Bytes of ONE compact color plane in the family's native packing."""
    return n * plane_width(family, m) * _family(family).cell_bytes


def working_set_bytes(family: str, n: int, m: int) -> int:
    """Modeled scoped VMEM of the resident kernel (module docstring)."""
    f = _family(family)
    return padded_cells(family, n, plane_width(family, m)) \
        * f.resident_bytes


def plan_resident(family: str, n: int, m: int) -> Optional[ResidentPlan]:
    """Fit decision for one (engine family, lattice) pair.

    Returns a :class:`ResidentPlan` when the modeled working set fits
    :data:`VMEM_LIMIT_BYTES` (read at call time so tests can move the
    fallback boundary), else ``None``.  A (family, lattice) demoted by
    the dispatch-recovery layer (``resilience.degrade``, after a runtime
    RESOURCE_EXHAUSTED launch) never fits again this process, whatever
    the model says.
    """
    attrs = decision_attrs(family, n, m)
    if TRACER.enabled:
        TRACER.instant("planner.decide", **attrs)
    if not attrs["fits_vmem"] or attrs.get("demoted"):
        return None
    return ResidentPlan(family=family, n=n, m=m,
                        plane_bytes=attrs["plane_bytes"],
                        working_set_bytes=attrs["working_set_bytes"],
                        budget_bytes=attrs["budget_bytes"])


def decision_attrs(family: str, n: int, m: int) -> dict:
    """The planner's decision and its budget arithmetic as one flat
    JSON-scalar dict -- the SINGLE rendering shared by the ``--dry-run``
    plan (``repro.api.session.describe``), the ``planner.decide`` trace
    instant, and the engines' ``dispatch`` span attributes, so the three
    can never disagree about the tier.
    """
    budget = VMEM_LIMIT_BYTES
    ws = working_set_bytes(family, n, m)
    attrs = {"family": family, "fits_vmem": ws <= budget,
             "plane_bytes": plane_bytes(family, n, m),
             "working_set_bytes": ws, "budget_bytes": budget}
    demoted = degrade.demotion_reason(family, n, m)
    if demoted is not None:
        attrs["demoted"] = True
        attrs["reason"] = (f"demoted to per-half-sweep fallback tier: "
                           f"{demoted}")
    elif ws > budget:
        attrs["reason"] = (f"working set {ws} B exceeds VMEM limit "
                           f"{budget} B: per-half-sweep fallback tier")
    return attrs


def max_square_lattice(family: str) -> int:
    """Largest square side n the engine accepts (a multiple of the
    family's lattice step) that runs resident -- the fallback boundary,
    for docs, tests and the chip smoke run (DESIGN.md S9 table)."""
    step = _family(family).lattice_step
    n = step
    while working_set_bytes(family, n + step, n + step) \
            <= VMEM_LIMIT_BYTES:
        n += step
    return n if working_set_bytes(family, n, n) <= VMEM_LIMIT_BYTES else 0
