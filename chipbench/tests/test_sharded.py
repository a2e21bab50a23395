"""The check of the four-chip cell ``multispin.shard4`` on four virtual
CPU devices, at 64 x 256.

Runs in a child process, since the number of host devices is fixed when
JAX starts.  The per-half-sweep sharded tier is forced by demoting the
lattice from the sharded resident tier, as a chip-size shard is."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from chipbench import run
from repro.resilience import degrade

cell = run.load_cell("multispin.shard4")
cell["config"] = dict(cell["config"], n=64, m=256)
degrade.demote("multispin", 64, 256, "test: per-half-sweep sharded tier")
from repro.core import distributed as dist
fault = {fault!r}
if fault == "exchange_left_out":
    def local(op, row_axes, col_axes):
        return op[-1:, :], op[:1, :], op[:, -1:], op[:, :1]
    dist._exchange_halos = local
elif fault != "none":
    import jax.numpy as jnp
    make = dist.make_packed_ising_step

    def faulty(*args, **kwargs):
        step, sharding = make(*args, **kwargs)

        def run(b, w, beta, off):
            old = (jnp.array(b, copy=True), jnp.array(w, copy=True))
            if fault == "state_unchanged":
                return old
            nb, nw = step(b, w, beta, off)
            if fault == "half_lattice_left_out":
                h = nb.shape[0] // 2
                return (jnp.concatenate([nb[:h], old[0][h:]]),
                        jnp.concatenate([nw[:h], old[1][h:]]))
            return nb.at[3, 1].set(nb[3, 1] ^ jnp.uint32(1 << 8)), nw
        return run, sharding
    dist.make_packed_ising_step = faulty
r = run.run_cell(cell, 2 ** 31 + 5, 0.5, trace={trace},
                 control={control}, require_chip=False,
                 log=lambda msg: None)
print(json.dumps(r))
"""


def _run(fault, trace=False, control=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=str(ROOT),
                                            src=str(ROOT / "src"),
                                            fault=fault, trace=trace,
                                            control=control)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("exchange_left_out", False),
    ("state_unchanged", False), ("half_lattice_left_out", False),
    ("spin_altered", False)])
def test_sharded_check(fault, correct):
    r = _run(fault)
    assert r["correct"] is correct, r["checks"]
    assert r["device"]["count"] == 4


def test_sharded_traced_run():
    r = _run("none", trace=True)
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["dispatches_per_sweep"]["value"] == 0.125


def test_sharded_control_is_not_correct():
    r = _run("none", control=True)
    assert r["correct"] is False
    assert r["checks"]["spins_differ"]["value"] > 0
