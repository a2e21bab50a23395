"""The readers of the program's compile account: nothing where the
program keeps no such counter (as before it did), the value where it
does, and both reported by a traced run of a cell."""
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
READERS = [("compile_s", "compile_ns", 2_500_000_000, 2.5),
           ("compile_cache_misses", "compile_cache_misses", 3, 3)]


@pytest.mark.parametrize("name,counter,count,value", READERS)
def test_reader_without_and_with_the_counter(name, counter, count, value,
                                             monkeypatch):
    import repro.telemetry as tel
    from repro.telemetry.metrics import MetricsRegistry
    read = run._load_reader(ROOT / "chipbench" / "metrics" / f"{name}.py",
                            name)
    bare = MetricsRegistry()
    monkeypatch.setattr(tel, "REGISTRY", bare)
    assert read({}) is None
    assert counter not in bare.snapshot()["counters"]  # not created
    kept = MetricsRegistry()
    kept.counter(counter).inc(count)
    monkeypatch.setattr(tel, "REGISTRY", kept)
    assert read({}) == value


def test_traced_run_reports_the_compile_account():
    cell = run.load_cell("multispin.sweep")
    cell["config"] = dict(cell["config"], n=32, m=64)
    r = run.run_cell(cell, 2 ** 33 + 5, 0.3, trace=True,
                     require_chip=False, log=lambda msg: None)
    assert r["correct"] is True, r["checks"]
    metrics = r["metrics"]
    assert metrics["compile_s"]["unit"] == "s"
    assert metrics["compile_s"]["value"] > 0
    assert metrics["compile_cache_misses"]["value"] >= 0
