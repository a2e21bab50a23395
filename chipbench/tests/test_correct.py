"""The check that decides ``correct``, on the CPU at a tiny lattice.

Each test drives a whole run of a cell (set-up, window, replay) with the
look for a chip skipped and the lattice cut to 64 x 128, and reads
``correct``: true for the program as it is, false for the control (the
bfloat16 reference in the program's place) and for each fault planted
under the timed path."""
import jax.numpy as jnp
import pytest

from chipbench import run

CELLS = ("multispin.sweep", "stencil.sweep", "multispin.measure")
SEED = 2 ** 31 + 977


def small(name):
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], n=64, m=128)
    return cell


def run_small(name, **kw):
    return run.run_cell(small(name), SEED, 0.5, require_chip=False,
                        log=lambda msg: None, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = run_small(name)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["spins_differ"]["value"] == 0
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    r = run_small(name, control=True)
    assert r["correct"] is False
    assert r["checks"]["spins_differ"]["value"] > 0
    if name == "multispin.measure":
        assert r["checks"]["e_gap"]["value"] > r["checks"]["e_gap"]["limit"]


def _unchanged(orig):
    def sweep_fn(self, state, inv_temp, seed, start_offset, n_sweeps):
        return tuple(state)
    return sweep_fn


def _half(orig):
    def sweep_fn(self, state, inv_temp, seed, start_offset, n_sweeps):
        new = orig(self, state, inv_temp, seed, start_offset, n_sweeps)
        half = state[0].shape[0] // 2
        return tuple(jnp.concatenate([a[:half], b[half:]])
                     for a, b in zip(new, state))
    return sweep_fn


def _altered(orig):
    def sweep_fn(self, state, inv_temp, seed, start_offset, n_sweeps):
        b, w = orig(self, state, inv_temp, seed, start_offset, n_sweeps)
        if b.dtype == jnp.uint32:
            return b.at[3, 1].set(b[3, 1] ^ jnp.uint32(1 << 8)), w
        return b.at[3, 1].set(-b[3, 1]), w
    return sweep_fn


FAULTS = {"state_unchanged": _unchanged, "half_lattice_left_out": _half,
          "spin_altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_under_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.core.engine import CounterEngine
    monkeypatch.setattr(CounterEngine, "sweep_fn",
                        FAULTS[fault](CounterEngine.sweep_fn))
    r = run_small(name)
    assert r["correct"] is False, (fault, r["checks"])


@pytest.mark.parametrize("field", ["m", "e"])
def test_altered_observable_is_not_correct(field, monkeypatch):
    """A sample altered where it is produced: the observable computed
    from the black plane alone."""
    from repro.core import lattice as lat
    from repro.core import multispin as ms
    from repro.core import observables as obs
    from repro.core.engine import MultispinEngine

    def observables(self, state, inv_temp):
        b, w = ms.unpack_lattice(*state)
        good = {"m": obs.magnetization_full(lat.merge_checkerboard(b, w)),
                "e": obs.energy_per_spin_full(
                    lat.merge_checkerboard(b, w))}
        bad = {"m": obs.magnetization_full(b),
               "e": obs.energy_per_spin_full(lat.merge_checkerboard(b, b))}
        return {**good, field: bad[field]}

    monkeypatch.setattr(MultispinEngine, "observables", observables)
    r = run_small("multispin.measure")
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["spins_differ"]["value"] == 0
    gap = r["checks"][f"{field}_gap"]
    assert gap["value"] > gap["limit"]
