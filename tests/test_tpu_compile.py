"""Ahead-of-time compiles of the Pallas sweep kernels for TPU v5e.

Interpret mode, which every other test runs, accepts kernels that
Mosaic refuses: unsupported casts and dtypes, and working sets over the
scoped-VMEM limit.  These tests compile the kernels for a described
``v5e:2x2`` topology, no chip attached, at the sizes the chip runs:

* each family's per-half-sweep kernel at 32768 lattice columns, with the
  block height ``kernels.resident.block_plan`` picks;
* each resident kernel at the largest lattice the planner admits;
* one ``repro.dist`` shard kernel at a planned four-chip shard.

Each compiles under the ``vmem_limit_bytes`` the planner passes, so a
pass also checks the planner's VMEM model at its boundary.  The
compiles run concurrently in one module fixture (they are minutes of
CPU serially), with the persistent compilation cache off: a compile for
a described chip cannot be read back from it.
"""
import concurrent.futures
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import resident
from repro.kernels.names import kernel_name

BLOCKED = ("stencil", "multispin", "bitplane")
CHIP_COLUMNS = 32768
FOUR_CHIP_LATTICE = 1024


def _plane(family, n, m):
    dtype = jnp.int8 if family == "stencil" else jnp.uint32
    return (n, resident.plane_width(family, m)), dtype


def _blocked_call(family):
    from repro.kernels.bitplane.bitplane import bitplane_update
    from repro.kernels.multispin.multispin import multispin_update
    from repro.kernels.stencil.stencil import stencil_update
    fn = {"stencil": stencil_update, "multispin": multispin_update,
          "bitplane": bitplane_update}[family]
    n = m = CHIP_COLUMNS
    rows = resident.block_plan(family, n, m).block_rows
    shape, dtype = _plane(family, n, m)
    return (lambda t, o: fn(t, o, 0.44, is_black=True, seed=3, offset=2,
                            block_rows=rows)), [(shape, dtype)] * 2


def _resident_call(family):
    from repro.kernels.bitplane.resident import bitplane_sweeps_resident
    from repro.kernels.multispin.resident import multispin_sweeps_resident
    from repro.kernels.stencil.resident import stencil_sweeps_resident
    fn = {"stencil": stencil_sweeps_resident,
          "multispin": multispin_sweeps_resident,
          "bitplane": bitplane_sweeps_resident}[family]
    n = resident.max_square_lattice(family)
    shape, dtype = _plane(family, n, n)
    return (lambda b, w: fn(b, w, 0.44, n_sweeps=3, seed=3,
                            start_offset=2)), [(shape, dtype)] * 2


def _shard_call():
    from repro.core import multispin as ms
    from repro.dist import kernels as dk
    from repro.dist.planner import plan_shard_resident
    plan = plan_shard_resident("multispin", FOUR_CHIP_LATTICE,
                               FOUR_CHIP_LATTICE, 2, 2)
    assert plan is not None
    ext = (plan.n_loc + 2 * plan.halo, plan.w_loc + 2 * plan.halo)
    return (lambda b, w, widx: dk.multispin_shard_sweeps(
        b, w, ms.acceptance_thresholds(0.44), widx, n_sweeps=plan.k,
        seed=3, start_offset=2)), [(ext, jnp.uint32)] * 3


CASES = ([f"blocked-{f}" for f in BLOCKED]
         + [f"resident-{f}" for f in BLOCKED] + ["dist-multispin"])


def _call(case):
    kind, family = case.split("-")
    if kind == "blocked":
        return _blocked_call(family)
    if kind == "resident":
        return _resident_call(family)
    return _shard_call()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    """case -> compiled text or the exception its compile raised."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def compile_case(case):
        fn, shapes = _call(case)
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        try:
            return jax.jit(fn).lower(*args).compile().as_text()
        except Exception as e:  # reported by the case's own test
            return e

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with concurrent.futures.ThreadPoolExecutor(len(CASES)) as pool:
            return dict(zip(CASES, pool.map(compile_case, CASES)))
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(compiled, case):
    out = compiled[case]
    if isinstance(out, Exception):
        raise out
    assert "tpu_custom_call" in out


@pytest.mark.parametrize("case", CASES)
def test_compiled_kernel_op_carries_its_name(compiled, case):
    """The compiled op is named from ``kernels.names``, which is the
    name a TPU trace gives the kernel's events."""
    out = compiled[case]
    if isinstance(out, Exception):
        raise out
    kind, family = case.split("-")
    tier = {"blocked": "stream", "resident": "resident",
            "dist": "shard_resident"}[kind]
    name = kernel_name(family, tier)
    ops = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", ln).group(1)
           for ln in out.splitlines()
           if 'custom_call_target="tpu_custom_call"' in ln]
    assert ops and all(re.fullmatch(rf"{name}(\.\d+)?", op)
                       for op in ops), ops


def test_planner_sizes_are_chip_sized():
    """The compiled cases are the sizes the chip runs: the resident
    boundary and the four-chip shard are not toy lattices."""
    assert resident.max_square_lattice("stencil") >= 1024
    assert resident.max_square_lattice("multispin") >= 2048
    assert resident.max_square_lattice("bitplane") >= 512
    for family in BLOCKED:
        plan = resident.block_plan(family, CHIP_COLUMNS, CHIP_COLUMNS)
        assert plan.working_set_bytes <= plan.vmem_limit_bytes
