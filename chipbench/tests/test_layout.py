"""Files found by name, the failures that name what is missing, the
result line's schema, and no result without a chip."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def checkout(tmp_path):
    """BENCHMARK.json and chipbench/ alone, copied to ``tmp_path``."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def edit_bench(root, fn):
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    fn(bench)
    path.write_text(json.dumps(bench))


@pytest.mark.parametrize("name", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_finds_its_files(name):
    cell = run.load_cell(name)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["traffic"]["kind"] in ("sweep", "measure")
    assert {m["name"] for m in cell["end_to_end"]} >= {"flips_per_ns",
                                                     "setup_s"}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    assert set(cell["readers"]) == {m["name"] for m in cell["per_layer"]}


def test_missing_config_fails_by_name(tmp_path):
    root = checkout(tmp_path)
    (root / "chipbench" / "configs" / "ising2d-stencil-65536x32768.json").unlink()
    with pytest.raises(run.CellError, match="ising2d-stencil-65536x32768"):
        run.load_cell("stencil.sweep", root)


def test_missing_traffic_fails_by_name(tmp_path):
    root = checkout(tmp_path)
    edit_bench(root, lambda b: b["workloads"][0].update(traffic="burst-9"))
    name = json.loads((root / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    with pytest.raises(run.CellError, match="burst-9"):
        run.load_cell(name, root)


def test_missing_metric_reader_fails_by_name(tmp_path):
    root = checkout(tmp_path)
    (root / "chipbench" / "metrics" / "device_idle_share.py").unlink()
    with pytest.raises(run.CellError, match="device_idle_share"):
        run.load_cell("multispin.sweep", root)


def test_unknown_workload_fails_by_name():
    with pytest.raises(run.CellError, match="no.such.cell"):
        run.load_cell("no.such.cell")


@pytest.mark.parametrize("kind,config", [
    ("layout", "ising2d-stencil-65536x32768"),
    ("stream", "ising2d-multispin-65536")])
def test_missing_plugin_fails_by_name(tmp_path, kind, config, monkeypatch):
    """A layout or stream with no file fails in load_cell, before any
    JAX work."""
    import jax
    root = checkout(tmp_path)
    path = root / "chipbench" / "configs" / f"{config}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **{kind: "nibble-9"})))
    workload = {w["config"]: w["name"] for w in json.loads(
        (root / "BENCHMARK.json").read_text())["workloads"]}[config]

    def no_jax(*args, **kwargs):
        raise AssertionError("JAX work before the missing file was found")

    monkeypatch.setattr(jax, "jit", no_jax)
    monkeypatch.setattr(jax, "devices", no_jax)
    with pytest.raises(run.CellError, match=rf"{kind}s/nibble-9\.py"):
        run.load_cell(workload, root)


#: the bitplane engine's layout and stream, as files a new cell brings
BITPLANE = {"layouts/bits32.py": "layout_bits32.py",
            "streams/site4.py": "stream_site4.py"}
N, M = 64, 128


def add_cell(root, case):
    """Add, as new files and new entries alone, a cell of ``case``
    (``multispin``, ``bitplane`` or ``bitplane.measure``) at 64 x 128
    with a per-layer metric ``flips_per_sweep``; returns its name."""
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "ising2d-multispin-32768.json")
                     .read_text())
    cfg.update(name=f"ising2d-{case}-tiny", n=N, m=M)
    if case.startswith("bitplane"):
        cfg.update(engine="bitplane_pallas", layout="bits32",
                   stream="site4", arrays=["black_bits", "white_bits"])
        for dst, src in BITPLANE.items():
            shutil.copy(DATA / src, cb / dst)
    (cb / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "sweep-k2.json").write_text(json.dumps(
        {"kind": "sweep", "sweeps_per_call": 2}))
    (cb / "metrics" / "flips_per_sweep.py").write_text(
        "def read(ctx):\n    return ctx['flips'] / ctx['sweeps']\n")
    name = f"tiny.{case}"

    def add(bench):
        bench["configs"].append(
            {"name": cfg["name"], "source": "test",
             "file": f"chipbench/configs/{cfg['name']}.json",
             "reduced": ["n", "m"], "why": "test"})
        bench["workloads"].append(
            {"name": name, "config": cfg["name"],
             "traffic": "measure-e1" if case.endswith("measure")
             else "sweep-k2", "chips": 1, "why": "test"})
        bench["per_layer"].append(
            {"name": "flips_per_sweep", "unit": "1/sweep",
             "better": "higher", "source": "host_clock",
             "layer": "harness", "moves": "flips_per_ns",
             "workloads": [name]})
    edit_bench(root, add)
    return name


def run_new(root, name, **kw):
    return run.run_cell(run.load_cell(name, root), 5, 0.3,
                        require_chip=False, log=lambda msg: None, **kw)


@pytest.mark.parametrize("case,lattices", [
    ("multispin", 1), ("bitplane", 32), ("bitplane.measure", 32)])
def test_new_cell_from_files_alone(tmp_path, case, lattices):
    """A config, a traffic, a metric file (and, for bitplane, a layout
    and a stream file) plus new entries in BENCHMARK.json make a cell; no
    harness code changes.  Flips count every lattice the state holds."""
    root = checkout(tmp_path)
    name = add_cell(root, case)
    r = run_new(root, name, trace=True)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["spins_differ"]["value"] == 0
    assert r["metrics"]["flips_per_sweep"]["value"] == N * M * lattices
    if case.endswith("measure"):
        assert set(r["checks"]) >= {"m_gap", "e_gap"}


#: lattices the reference replays together: all 32, or groups of 5 (the
#: last of 2), as a state too large to replay at once is
GROUPS = [32, 5]


@pytest.mark.parametrize("case", ["bitplane", "bitplane.measure"])
def test_new_bitplane_cell_in_groups(tmp_path, case, monkeypatch):
    monkeypatch.setattr(run, "REPLAY_CELLS", N * M * 5)
    root = checkout(tmp_path)
    r = run_new(root, add_cell(root, case))
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("per", GROUPS)
@pytest.mark.parametrize("case", ["bitplane", "bitplane.measure"])
def test_new_bitplane_cell_control_is_not_correct(tmp_path, case, per,
                                                  monkeypatch):
    monkeypatch.setattr(run, "REPLAY_CELLS", N * M * per)
    root = checkout(tmp_path)
    r = run_new(root, add_cell(root, case), control=True)
    assert r["correct"] is False
    assert r["checks"]["spins_differ"]["value"] > 0


@pytest.mark.parametrize("per", GROUPS)
def test_new_bitplane_cell_catches_one_replica(tmp_path, per, monkeypatch):
    """Cells of replica 5 alone flipped where the sweep returns: the
    check counts each of them, and no other."""
    import jax.numpy as jnp

    from repro.core.engine import CounterEngine
    planted = [(0, 3, 1), (0, 10, 7), (1, 0, 0)]
    orig = CounterEngine.sweep_fn

    def sweep_fn(self, state, inv_temp, seed, start_offset, n_sweeps):
        new = list(orig(self, state, inv_temp, seed, start_offset,
                        n_sweeps))
        for c, i, k in planted:
            new[c] = new[c].at[i, k].set(new[c][i, k] ^ jnp.uint32(1 << 5))
        return tuple(new)

    monkeypatch.setattr(CounterEngine, "sweep_fn", sweep_fn)
    monkeypatch.setattr(run, "REPLAY_CELLS", N * M * per)
    root = checkout(tmp_path)
    r = run_new(root, add_cell(root, "bitplane"))
    assert r["correct"] is False
    assert r["checks"]["spins_differ"]["value"] == len(planted)


def test_result_line_schema():
    cell = run.load_cell("multispin.sweep")
    cell["config"] = dict(cell["config"], n=32, m=64)
    for trace in (False, True):
        r = run.run_cell(cell, 2 ** 33 + 1, 0.3, trace=trace,
                         require_chip=False, log=lambda msg: None)
        line = json.loads(json.dumps(r))
        assert list(line)[-1] == "checks"
        for key in ("correct", "attempted", "failed", "metrics", "device"):
            assert key in line
        assert set(line["device"]) >= {"platform", "kind", "count",
                                       "memory_peak_bytes"}
        for c in line["checks"].values():
            assert set(c) == {"value", "limit"}
        for m in line["metrics"].values():
            assert set(m) == {"value", "unit"}
        if trace:
            assert set(line["device"]) >= {"busy_s", "window_s"}
            assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
            assert "flips_per_ns" not in line["metrics"]
        else:
            assert set(line["metrics"]) == {"flips_per_ns", "setup_s"}


def _cli(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "multispin.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    root = checkout(tmp_path)
    p = _cli(root, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,chips,ok", [
    ("TPU v5 lite", 1, True), ("TPU v5 lite", 4, False),
    ("TPU v9 imaginary", 1, False)])
def test_device_must_be_in_peaks(kind, chips, ok, monkeypatch):
    import jax
    peaks = run.load_cell("multispin.sweep")["peaks"]
    assert not any("cpu" in k.lower() for k in peaks["devices"])
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(kind)])
    if ok:
        assert run._devices(chips, True, peaks)[0].device_kind == kind
    else:
        with pytest.raises(run.NoChip):
            run._devices(chips, True, peaks)
