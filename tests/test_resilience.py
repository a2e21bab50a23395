"""Resilience subsystem: CRC32C integrity, crash topologies, fault
injection, retry/demotion recovery, and bit-exact supervised resume
(DESIGN.md S13)."""
import os
import signal

import numpy as np
import pytest

import repro.telemetry as tel
from repro.api import (BatchSpec, EngineSpec, LatticeSpec, MeshSpec,
                       RunSpec)
from repro.api.session import Session
from repro.ckpt import (Checkpointer, CheckpointError,
                        CheckpointIntegrityError)
from repro.resilience import (FaultPlanError, SimulatedResourceExhausted,
                              Supervisor, SupervisorError,
                              TransientDispatchError, degrade, faults,
                              integrity)


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Faults and demotions are process-global by design; tests must
    not leak them into each other."""
    faults.clear()
    degrade.reset_demotions()
    yield
    faults.clear()
    degrade.reset_demotions()


@pytest.fixture
def nosleep(monkeypatch):
    """Retry without wall-clock backoff."""
    monkeypatch.setattr(degrade, "DEFAULT_POLICY",
                        degrade.RetryPolicy(sleep=lambda d: None))


def _spec(engine="multispin", n=16, m=32, seed=7, **kw):
    return RunSpec(lattice=LatticeSpec(n, m),
                   engine=EngineSpec(engine),
                   temperature=2.1, seed=seed, **kw)


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------

def test_crc32c_known_vectors():
    # canonical CRC-32C check values (RFC 3720 appendix / kernel tests)
    assert integrity.crc32c(b"") == 0
    assert integrity.crc32c(b"123456789") == 0xE3069283
    assert integrity.crc32c(b"The quick brown fox jumps over "
                            b"the lazy dog") == 0x22620404


def test_crc32c_incremental_chaining():
    a, b = b"hello, ", b"world" * 500
    assert integrity.crc32c(b, integrity.crc32c(a)) \
        == integrity.crc32c(a + b)


def test_crc32c_ladder_matches_scalar_oracle():
    """The vectorized numpy ladder is property-tested against the
    byte-walk oracle across the threshold and odd lengths."""
    rng = np.random.default_rng(0)
    for n in (1, 7, 8, 2047, 2048, 2049, 65537):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        init = int(rng.integers(0, 2 ** 32))
        assert integrity._crc32c_numpy(data, init) \
            == integrity._crc32c_scalar(data, init), n


# ---------------------------------------------------------------------------
# crash topologies: latest_step must skip every invalid shape
# ---------------------------------------------------------------------------

def _save_steps(tmp_path, steps=(10, 20, 30)):
    ck = Checkpointer(str(tmp_path), keep=0)
    for s in steps:
        ck.save(s, {"a": np.arange(s, dtype=np.int64)})
    return ck


def test_latest_step_skips_kill_mid_write(tmp_path):
    ck = _save_steps(tmp_path)
    faults.kill_mid_write(ck.dir, 40)  # torn write: no DONE marker
    assert ck.latest_step() == 30


def test_latest_step_skips_truncated_arrays(tmp_path):
    ck = _save_steps(tmp_path)
    faults.truncate_arrays(ck.dir, 30)  # DONE present, payload torn
    problems = ck.validate_step(30)
    assert any("truncated" in p for p in problems), problems
    assert ck.latest_step() == 20


def test_latest_step_skips_stale_done(tmp_path):
    ck = _save_steps(tmp_path)
    faults.stale_done(ck.dir, 30)  # marker outlived its arrays
    assert any("stale" in p for p in ck.validate_step(30))
    assert ck.latest_step() == 20


def test_latest_step_skips_flipped_byte(tmp_path):
    ck = _save_steps(tmp_path)
    faults.flip_byte(ck.dir, 30)  # silent bit rot under a valid DONE
    assert any("CRC32C" in p for p in ck.validate_step(30))
    assert ck.latest_step() == 20


def test_latest_step_survives_pruning_race(tmp_path, monkeypatch):
    """``keep``-GC deleting a step between discovery and validation
    must make the walk move on, not crash."""
    ck = _save_steps(tmp_path)
    real = Checkpointer.all_steps

    def racy(self):
        return real(self) + [40]  # 40 was pruned right after listing

    monkeypatch.setattr(Checkpointer, "all_steps", racy)
    assert ck.latest_step() == 30
    step, arrays = ck.load_arrays()
    assert step == 30


def test_quarantine_and_fallback_restore(tmp_path):
    """A corrupt newest step is quarantined (kept for post-mortem,
    renamed out of discovery) and restore falls back to the previous
    good step; ``ckpt.quarantine`` accounts the action."""
    ck = _save_steps(tmp_path)
    faults.flip_byte(ck.dir, 30)
    before = tel.REGISTRY.counter("ckpt.quarantine").value
    step, arrays = ck.load_arrays()
    assert step == 20
    np.testing.assert_array_equal(arrays["a"],
                                  np.arange(20, dtype=np.int64))
    assert tel.REGISTRY.counter("ckpt.quarantine").value == before + 1
    names = sorted(os.listdir(ck.dir))
    assert "quarantine_step_0000000030" in names
    assert "step_0000000030" not in names


def test_explicit_step_integrity_error_names_problem(tmp_path):
    """Asking for exact bytes that fail verification must raise, not
    silently substitute another step."""
    ck = _save_steps(tmp_path)
    faults.flip_byte(ck.dir, 30)
    with pytest.raises(CheckpointIntegrityError, match="CRC32C"):
        ck.load_arrays(step=30)
    assert ck.all_steps() == [10, 20, 30]  # explicit: NOT quarantined


def test_verify_arrays_names_offending_key():
    a = {"good": np.arange(4), "bad": np.arange(8)}
    manifest = {"arrays": {k: integrity._array_record(v)
                           for k, v in a.items()}}
    a["bad"] = a["bad"] + 1
    problems = integrity.verify_arrays(a, manifest)
    assert len(problems) == 1 and "'bad'" in problems[0]
    assert integrity.verify_arrays(a, None) == []  # legacy: no manifest


def test_exhausted_checkpoints_raise_typed_error(tmp_path):
    ck = _save_steps(tmp_path, steps=(10,))
    faults.flip_byte(ck.dir, 10)
    with pytest.raises(CheckpointError, match="failed verification"):
        ck.load_arrays()
    with pytest.raises(CheckpointError, match="no checkpoint"):
        Checkpointer(str(tmp_path / "empty")).load_arrays()


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------

def test_fault_plan_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", '{"transient_dispatches": 2}')
    plan = faults.install_from_env()
    assert plan.transient_dispatches == 2
    assert faults.active_plan() is plan
    monkeypatch.setenv("REPRO_FAULTS", '{"bogus": 1}')
    with pytest.raises(FaultPlanError, match="unknown fault kind"):
        faults.install_from_env()
    monkeypatch.delenv("REPRO_FAULTS")
    faults.clear()
    assert faults.install_from_env() is None


@pytest.mark.parametrize("text,match", [
    ('{"transient_dispatches": 2', "malformed JSON"),
    ('[1, 2]', "must be a JSON object"),
    ('{"bogus": 1}', "unknown fault kind"),
    ('{"transient_dispatches": "two"}', "must be an integer"),
    ('{"transient_dispatches": true}', "must be an integer"),
    ('{"resident_oom": -1}', "must be >= 0"),
])
def test_fault_plan_failures_are_typed_and_diagnosable(text, match):
    """Every malformation is a FaultPlanError CARRYING the offending
    text -- a chaos job with a bad REPRO_FAULTS must fail loudly, not
    run faultless and pass vacuously."""
    with pytest.raises(FaultPlanError, match=match) as ei:
        faults.FaultPlan.from_json(text)
    assert ei.value.text == text
    assert repr(text) in str(ei.value)


# ---------------------------------------------------------------------------
# dispatch recovery: retry + demotion, bit-exact
# ---------------------------------------------------------------------------

def test_transient_retry_is_bit_exact(nosleep):
    ref = Session.open(_spec())
    ref.run(6)
    before = tel.REGISTRY.counter("resilience.retry").value
    s = Session.open(_spec())
    with faults.injected(faults.FaultPlan(transient_dispatches=2)) as p:
        s.run(6)
    assert p.fired == {"transient_dispatch": 2}
    assert tel.REGISTRY.counter("resilience.retry").value == before + 2
    assert s.state_digest() == ref.state_digest()


def test_retry_budget_exhausts(nosleep):
    s = Session.open(_spec())
    with faults.injected(faults.FaultPlan(transient_dispatches=99)):
        with pytest.raises(TransientDispatchError):
            s.run(4)
    # the default policy allows max_retries retries = 4 attempts
    assert faults.active_plan() is None  # fixture restores


def test_resident_oom_demotes_bit_exact():
    """A RESOURCE_EXHAUSTED launch demotes the (family, lattice) to the
    fallback tier, retries immediately, and the trajectory does not
    fork; a FRESH engine on the same lattice starts demoted too."""
    ref = Session.open(_spec("multispin_pallas"))
    assert ref.engine.resident_plan is not None
    ref.run(6)
    before = tel.REGISTRY.counter("resident.demote").value
    s = Session.open(_spec("multispin_pallas"))
    with faults.injected(faults.FaultPlan(resident_oom=1)) as p:
        s.run(6)
    assert p.fired == {"resident_oom": 1}
    assert s.engine.resident_plan is None
    assert s.state_digest() == ref.state_digest()
    assert tel.REGISTRY.counter("resident.demote").value == before + 1
    assert degrade.demotion_reason("multispin", 16, 32) is not None
    fresh = Session.open(_spec("multispin_pallas"))
    assert fresh.engine.resident_plan is None
    assert fresh.engine.resident_attrs["demoted"] is True
    assert "fallback" in fresh.engine.resident_attrs["reason"]


def test_ensemble_demotion_bit_exact():
    """The vmapped ensemble runner clears ITS jit cache on demotion
    (on_demote) so the retry re-traces the fallback tier."""
    batch = BatchSpec(temperatures=(2.0, 2.4))
    ref = Session.open(_spec("multispin_pallas", batch=batch))
    m_ref = ref.run(5)
    s = Session.open(_spec("multispin_pallas", batch=batch))
    with faults.injected(faults.FaultPlan(resident_oom=1)):
        m = s.run(5)
    np.testing.assert_array_equal(m, m_ref)
    assert s.state_digest() == ref.state_digest()


#: Mosaic's refusal of a kernel whose working set is over the limit
_MOSAIC_VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %pallas_call = custom-call(...), "
    'custom_call_target="tpu_custom_call". Scoped allocation with size '
    "28.90M and limit 16.00M exceeded scoped vmem limit by 12.90M.")


def test_mosaic_vmem_refusal_is_not_a_runtime_oom():
    exc = RuntimeError(_MOSAIC_VMEM_REFUSAL)
    assert degrade.is_compile_refusal(exc)
    assert not degrade.is_resident_oom(exc)
    assert not degrade.is_transient(exc)
    assert not degrade.is_compile_refusal(SimulatedResourceExhausted())


def test_mosaic_vmem_refusal_does_not_demote():
    """A compile refusal propagates: the resident tier is not demoted
    and nothing falls back in silence."""
    s = Session.open(_spec("multispin_pallas"))
    assert s.engine.resident_plan is not None
    before = tel.REGISTRY.counter("resident.demote").value

    def refused():
        raise RuntimeError(_MOSAIC_VMEM_REFUSAL)

    with pytest.raises(RuntimeError, match="scoped vmem limit"):
        degrade.run_dispatch(refused, engine=s.engine)
    assert s.engine.resident_plan is not None
    assert degrade.demotion_reason("multispin", 16, 32) is None
    assert tel.REGISTRY.counter("resident.demote").value == before


def test_simulated_oom_classifies_like_real():
    exc = SimulatedResourceExhausted()
    assert degrade.is_resident_oom(exc)
    assert not degrade.is_transient(exc)
    assert degrade.is_transient(TransientDispatchError("x"))
    assert degrade.is_transient(RuntimeError("UNAVAILABLE: queue"))


# ---------------------------------------------------------------------------
# supervisor: bit-exact resume across all three runner modes
# ---------------------------------------------------------------------------

def _stop_at(step):
    def hook(sup):
        if sup.session.step_count >= step:
            sup.request_stop()
    return hook


# key-based single (chunk-grid-sensitive), counter-based ensemble,
# sharded Philox -- one spec per Session runner mode
_MODE_SPECS = {
    "single": lambda: _spec("basic", n=16, m=16),
    "ensemble": lambda: _spec(batch=BatchSpec(temperatures=(2.0, 2.4))),
    "sharded": lambda: _spec("basic_philox", n=16, m=16,
                             mesh=MeshSpec((1, 1), ("data", "model"))),
}


@pytest.mark.parametrize("mode", sorted(_MODE_SPECS))
def test_supervised_resume_bit_exact(tmp_path, mode):
    """Interrupt at an arbitrary chunk, restore, continue: lattice and
    observables bit-for-bit vs an uninterrupted supervised run."""
    make = _MODE_SPECS[mode]
    ref = Supervisor(make(), str(tmp_path / "ref"), chunk=4,
                     every_sweeps=8).run(22)
    assert ref.completed and ref.status == "completed"

    d = str(tmp_path / "int")
    r1 = Supervisor(make(), d, chunk=4, every_sweeps=8,
                    on_chunk=_stop_at(12)).run(22)
    assert r1.status == "preempted" and r1.step_count == 12
    assert not r1.completed

    before = tel.REGISTRY.counter("resilience.resume").value
    sup2 = Supervisor(make(), d, chunk=4, every_sweeps=8)
    assert sup2.session.mode == mode
    assert sup2.resumed_from == 12
    assert tel.REGISTRY.counter("resilience.resume").value == before + 1
    r2 = sup2.run(22)
    assert r2.completed
    assert r2.digest == ref.digest
    # observables agree too, not just the digest
    ref_sess = Supervisor(make(), str(tmp_path / "ref"), chunk=4).session
    np.testing.assert_array_equal(
        np.asarray(sup2.session.full_lattice()),
        np.asarray(ref_sess.full_lattice()))
    np.testing.assert_array_equal(
        np.asarray(sup2.session.magnetization()),
        np.asarray(ref_sess.magnetization()))


@pytest.mark.parametrize("mode", sorted(_MODE_SPECS))
def test_supervised_resume_after_corruption(tmp_path, mode):
    """CRC-reject + fallback restore in every runner mode: the newest
    checkpoint is corrupted, resume falls back to the previous good
    step and still converges to the uninterrupted digest."""
    make = _MODE_SPECS[mode]
    ref = Supervisor(make(), str(tmp_path / "ref"), chunk=4).run(22)
    d = str(tmp_path / "chaos")
    r1 = Supervisor(make(), d, chunk=4, every_sweeps=4,
                    on_chunk=_stop_at(12)).run(22)
    assert r1.checkpoints_written[-2:] == [8, 12]
    faults.flip_byte(d, 12)
    sup = Supervisor(make(), d, chunk=4, every_sweeps=4)
    assert sup.resumed_from == 8
    assert sup.run(22).digest == ref.digest


def test_supervisor_rejects_spec_mismatch(tmp_path):
    d = str(tmp_path)
    Supervisor(_spec(seed=7), d, chunk=4, on_chunk=_stop_at(4)).run(8)
    with pytest.raises(SupervisorError, match="different spec"):
        Supervisor(_spec(seed=8), d, chunk=4)


def test_supervisor_requires_spec_or_checkpoint(tmp_path):
    with pytest.raises(SupervisorError, match="no spec"):
        Supervisor(None, str(tmp_path))


def test_supervisor_sigterm_checkpoints_and_resumes(tmp_path):
    """A real SIGTERM mid-run: the handler requests a stop, the loop
    checkpoints at the chunk boundary and reports preemption; rerunning
    resumes to the uninterrupted digest."""
    ref = Supervisor(_spec(), str(tmp_path / "ref"), chunk=4).run(12)
    d = str(tmp_path / "sig")

    def send_sigterm(sup):
        if sup.session.step_count == 4:
            os.kill(os.getpid(), signal.SIGTERM)

    r1 = Supervisor(_spec(), d, chunk=4,
                    on_chunk=send_sigterm).run(12)
    assert r1.status == "preempted"
    assert r1.stop_signal == signal.SIGTERM
    assert r1.checkpoints_written  # preemption persisted progress
    r2 = Supervisor(_spec(), d, chunk=4).run(12)
    assert r2.completed and r2.digest == ref.digest


def test_supervisor_resume_from_spec_in_checkpoint(tmp_path):
    """``Supervisor(None, dir)`` rebuilds the run entirely from the
    spec.json sidecar -- the CLI resume-without-flags path."""
    d = str(tmp_path)
    Supervisor(_spec(), d, chunk=4, on_chunk=_stop_at(4)).run(12)
    sup = Supervisor(None, d, chunk=4)
    assert sup.resumed_from == 4
    assert sup.session.spec.to_dict() == _spec().to_dict()
    assert sup.run(12).completed


def test_supervisor_zero_cadence_writes_no_periodic_steps(tmp_path):
    """Cadence off => no checkpoint I/O during the loop (the zero-
    hot-path-overhead contract the perf gate measures)."""
    d = str(tmp_path)
    res = Supervisor(_spec(), d, chunk=4).run(12)
    assert res.completed
    assert res.checkpoints_written == []  # fresh run, cadence off
    assert os.listdir(d) == []
