"""A run with the timed path broken underneath must come out not correct:
once for each fault a one-chip cell can have (there is no exchange between
chips to leave out). The numbers the tiny ``wordcount.drift.sat`` run reads
are pinned, for the program, its control and each fault."""

import pytest

import tiny
from repro.streams import backends, device, operators


def state_unchanged(monkeypatch):
    """The fused step returns the state it was given."""
    real = device._interval_step_add

    def step(vals, pres, *args, **kwargs):
        out = real(vals, pres, *args, **kwargs)
        return (vals, pres) + tuple(out[2:])
    monkeypatch.setattr(device, "_interval_step_add", step)


def half_batch(monkeypatch):
    """The stage processes only the first half of an interval's tuples."""
    real = backends.DeviceBackend.process_interval

    def process(self, keys, values=None, collect_emits=False):
        half = keys.shape[0] // 2
        if values is not None:
            values = values[:half]
        return real(self, keys[:half], values, collect_emits)
    monkeypatch.setattr(backends.DeviceBackend, "process_interval", process)


def answer_off_by_one(monkeypatch):
    """One count is one too high where it is produced: the first output of
    each count step."""
    real = operators.WordCount.device_finish

    def finish(self, counts, win0, slot0):
        cost, out, emit = real(self, counts, win0, slot0)
        out = out.copy()
        out[:1] += 1
        return cost, out, emit
    monkeypatch.setattr(operators.WordCount, "device_finish", finish)


FAULTS = [state_unchanged, half_batch, answer_off_by_one]


@pytest.mark.parametrize("cell", tiny.CELLS)
@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result, _ = tiny.run(cell)
    assert not result["correct"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


#: the eight numbers of the tiny ``wordcount.drift.sat`` run (seed
#: 2^31 + 17, 4 warm-up and 7 window intervals) as the reference read them
#: over every key of ``[0, domain)``, the dense oracle of
#: ``test_bench_reference.py``; ``control`` is the int16 window's
PINNED = {
    "program": dict.fromkeys(
        ["outputs_wrong", "emitted_gap", "loads_wrong", "triggers_wrong",
         "tables_wrong", "plans_over", "owners_wrong", "sizes_wrong"], 0),
    "control": {"outputs_wrong": 3, "emitted_gap": 6564771030.0,
                "loads_wrong": 0, "triggers_wrong": 0, "tables_wrong": 0,
                "plans_over": 0, "owners_wrong": 0, "sizes_wrong": 0},
    "state_unchanged": {"outputs_wrong": 10000, "emitted_gap": 2200844886.0,
                        "loads_wrong": 0, "triggers_wrong": 0,
                        "tables_wrong": 0, "plans_over": 0,
                        "owners_wrong": 0, "sizes_wrong": 0},
    "half_batch": {"outputs_wrong": 10000, "emitted_gap": 11610075526.0,
                   "loads_wrong": 165, "triggers_wrong": 0,
                   "tables_wrong": 0, "plans_over": 6, "owners_wrong": 1,
                   "sizes_wrong": 1},
    "answer_off_by_one": {"outputs_wrong": 1, "emitted_gap": 0.0,
                          "loads_wrong": 0, "triggers_wrong": 0,
                          "tables_wrong": 0, "plans_over": 0,
                          "owners_wrong": 0, "sizes_wrong": 0},
}


@pytest.mark.parametrize("fault", [None] + FAULTS,
                         ids=lambda f: f.__name__ if f else "program")
def test_checks_read_as_pinned(fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    # a pool of 7 window intervals that runs out well inside the window,
    # so every run covers the same intervals
    result, run = tiny.run("wordcount.drift.sat", seconds=600.0,
                           control=fault is None,
                           traffic_overrides={"pool_rate": 7000})
    assert len(run.intervals) == 7
    got = {k: c["value"] for k, c in result["checks"].items()}
    assert got == PINNED[fault.__name__ if fault else "program"]
    if fault is None:
        assert result["control"] == PINNED["control"]
