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


def test_new_cell_from_files_alone(tmp_path):
    """A config, a traffic and a metric file plus one workload entry make
    a cell; no harness code changes."""
    root = checkout(tmp_path)
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "ising2d-multispin-32768.json")
                     .read_text())
    cfg.update(name="ising2d-multispin-tiny", n=32, m=64)
    (cb / "configs" / "ising2d-multispin-tiny.json").write_text(
        json.dumps(cfg))
    (cb / "traffic" / "sweep-k2.json").write_text(json.dumps(
        {"kind": "sweep", "sweeps_per_call": 2}))
    (cb / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n    return ctx['calls'] / ctx['window_s']\n")

    def add(bench):
        bench["configs"].append(
            {"name": "ising2d-multispin-tiny", "source": "test",
             "file": "chipbench/configs/ising2d-multispin-tiny.json",
             "reduced": ["n", "m"], "why": "test"})
        bench["workloads"].append(
            {"name": "tiny.k2", "config": "ising2d-multispin-tiny",
             "traffic": "sweep-k2", "chips": 1, "why": "test"})
        bench["per_layer"].append(
            {"name": "calls_per_s", "unit": "1/s", "better": "higher",
             "source": "host_clock", "layer": "facade and engines",
             "moves": "flips_per_ns", "workloads": ["tiny.k2"]})
    edit_bench(root, add)
    cell = run.load_cell("tiny.k2", root)
    assert cell["traffic"]["sweeps_per_call"] == 2
    r = run.run_cell(cell, 5, 0.3, trace=True, require_chip=False,
                     log=lambda msg: None)
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["calls_per_s"]["value"] > 0


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
