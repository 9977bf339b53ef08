"""Every cell end to end at a tiny size on the CPU: the harness drives the
program, the reference agrees, and the control does not."""

import pytest

import tiny


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_cell_is_correct_and_its_control_is_not(cell):
    result, _ = tiny.run(cell, control=True)
    checks = result["checks"]
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(c["value"] == 0 for c in checks.values())
    # the result line's keys the benchmark's contract fixes, checks last
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"]
    assert len(result["metrics"]) >= 2
    # the control (int16 window) fails at least one number
    assert any(v > 0 for v in result["control"].values()), result["control"]


def test_traced_run_reports_host_metrics():
    """On the CPU the trace has no TPU plane: device metrics are left out,
    the host-clock and counter metrics are there."""
    result, _ = tiny.run("wordcount.drift.sat", trace=True)
    assert result["correct"]
    assert "interval_ms.sat" in result["metrics"]
    assert "plan_ms.sat" in result["metrics"]
    assert "device_idle_pct.sat" not in result["metrics"]


def test_without_a_sizes_file_a_cell_runs_at_the_default_sizes(
        tmp_path, monkeypatch):
    assert not (tiny.SIZES / "paper-wordcount.json").exists()
    monkeypatch.setattr(tiny, "SIZES", tmp_path)
    assert tiny.sizes("wordcount.drift.sat") == (tiny.TINY, tiny.TRAFFIC)
    result, run = tiny.run("wordcount.drift.sat")
    assert result["correct"], result["checks"]
    assert run.mix.warmup_intervals == tiny.TRAFFIC["warmup_intervals"]
    assert result["attempted"] == tiny.TINY["tuples"] * len(run.intervals)
