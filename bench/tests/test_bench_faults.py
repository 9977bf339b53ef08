"""A run with the timed path broken underneath must come out not correct:
once for each fault a one-chip cell can have (there is no exchange between
chips to leave out)."""

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

