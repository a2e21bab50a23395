"""The name of every Pallas kernel: one per family and tier.

Each ``pl.pallas_call`` of the sweep kernels passes ``name=`` from this
table.  Mosaic's custom call carries it as ``kernel_name``, and the
compiled HLO names the op after it (``%multispin_stream.1 =
custom-call(...)``), so a device trace tells the kernels apart by
family and tier.  The tiers:

* ``stream``         -- one half-sweep per call, row blocks streamed
  from HBM (``kernels/<family>/<family>.py``);
* ``resident``       -- k sweeps per call on planes held in VMEM
  (``kernels/<family>/resident.py``);
* ``shard_resident`` -- k sweeps of one halo-extended shard in VMEM
  (``dist/kernels.py``).
"""
from __future__ import annotations

KERNEL_NAMES = {
    ("stencil", "stream"): "stencil_stream",
    ("stencil", "resident"): "stencil_resident",
    ("stencil", "shard_resident"): "stencil_shard_resident",
    ("multispin", "stream"): "multispin_stream",
    ("multispin", "resident"): "multispin_resident",
    ("multispin", "shard_resident"): "multispin_shard_resident",
    ("bitplane", "stream"): "bitplane_stream",
    ("bitplane", "resident"): "bitplane_resident",
    ("bitplane", "shard_resident"): "bitplane_shard_resident",
    ("tensorcore", "stream"): "tensorcore_stream",
}


def kernel_name(family: str, tier: str) -> str:
    """The ``name=`` of the ``pallas_call`` of ``family`` at ``tier``."""
    return KERNEL_NAMES[(family, tier)]
