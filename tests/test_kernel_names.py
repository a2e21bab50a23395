"""Every Pallas sweep kernel carries its name from ``kernels.names``.

Each family and tier is lowered for the TPU (Mosaic), on any host: no
chip and no TPU compile are needed, only the lowering that writes the
``tpu_custom_call``.  Its ``kernel_name`` is the name the compiled HLO
op, and so the device trace, carries.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import multispin as ms
from repro.kernels.names import KERNEL_NAMES, kernel_name

N = 32
W = 128
BETA = 0.44


def _plane(dtype):
    return jax.ShapeDtypeStruct((N, W), dtype)


def _stream(family):
    from repro.kernels.bitplane.bitplane import bitplane_update
    from repro.kernels.multispin.multispin import multispin_update
    from repro.kernels.stencil.stencil import stencil_update
    fn = {"stencil": stencil_update, "multispin": multispin_update,
          "bitplane": bitplane_update}[family]
    dtype = jnp.int8 if family == "stencil" else jnp.uint32
    return (lambda t, o: fn(t, o, BETA, is_black=True, seed=3, offset=2,
                            block_rows=N // 2)), [_plane(dtype)] * 2


def _resident(family):
    from repro.kernels.bitplane.resident import bitplane_sweeps_resident
    from repro.kernels.multispin.resident import multispin_sweeps_resident
    from repro.kernels.stencil.resident import stencil_sweeps_resident
    fn = {"stencil": stencil_sweeps_resident,
          "multispin": multispin_sweeps_resident,
          "bitplane": bitplane_sweeps_resident}[family]
    dtype = jnp.int8 if family == "stencil" else jnp.uint32
    return (lambda b, w: fn(b, w, BETA, n_sweeps=2, seed=3,
                            start_offset=2)), [_plane(dtype)] * 2


def _shard_resident(family):
    from repro.dist import kernels as dk
    kw = dict(n_sweeps=2, seed=3, start_offset=2)
    u32 = _plane(jnp.uint32)
    if family == "stencil":
        return (lambda b, w, g: dk.stencil_shard_sweeps(
            b, w, BETA, g, **kw)), [_plane(jnp.int8)] * 2 + [u32]
    thr = ms.acceptance_thresholds(BETA)
    if family == "multispin":
        return (lambda b, w, g: dk.multispin_shard_sweeps(
            b, w, thr, g, **kw)), [u32] * 3
    return (lambda b, w, g, lane: dk.bitplane_shard_sweeps(
        b, w, thr, g, lane, **kw)), [u32] * 4


def _tensorcore():
    from repro.kernels.tensorcore.tensorcore import tensorcore_update
    keys = ("00", "01", "10", "11")
    return (lambda *p: tensorcore_update(dict(zip(keys, p)), "black",
                                         BETA, seed=3, offset=2)), \
        [jax.ShapeDtypeStruct((W, W), jnp.bfloat16)] * 4


def _call(family, tier):
    if family == "tensorcore":
        return _tensorcore()
    return {"stream": _stream, "resident": _resident,
            "shard_resident": _shard_resident}[tier](family)


@pytest.mark.parametrize("family,tier", sorted(KERNEL_NAMES))
def test_pallas_call_carries_its_table_name(family, tier):
    fn, args = _call(family, tier)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    names = set(re.findall(r'kernel_name = "([^"]*)"', text))
    assert names == {kernel_name(family, tier)}


def test_table_names_are_family_and_tier():
    for (family, tier), name in KERNEL_NAMES.items():
        assert name == f"{family}_{tier}"
    assert len(set(KERNEL_NAMES.values())) == len(KERNEL_NAMES) == 10
