"""``chip_smoke.py`` end to end at a tiny size on the CPU.

The script's full size is for the chip; here every phase runs with a 4096-id
domain and 20k tuples per interval, Pallas kernels in interpret mode, so a
wrong path, argument or comparison shows up before chip time is spent. The
comparisons must also be able to fail: each is fed a result that differs in
one field. And the script must refuse to run anywhere but on a TPU.
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Size(domain=1 << 12, tuples=20_000, intervals=7, hot=16,
                      shift_every=2, buckets=64, kill_interval=5,
                      stats_tuples=4096, stats_keys=1024, tables=(128, 256))


@pytest.fixture(scope="module")
def counter(smoke):
    return smoke.CompileCounter()


def test_smoke_refuses_to_run_without_a_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "not a TPU" in err


def test_single_chip_phases_pass_at_tiny_size(smoke, tiny, counter, capsys):
    device = smoke.run(tiny, 1, seed=3, counter=counter)
    assert device == {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind,
                      "count": jax.device_count()}
    out = capsys.readouterr().out
    for phase in ("phase 2", "phase 3", "phase 4", "phase 5"):
        assert f"{phase}" in out


def test_sharded_phase_passes_at_tiny_size(smoke, tiny, counter):
    n_shards = min(4, jax.device_count())
    trace = smoke.make_trace(tiny, 4)
    smoke.run_phase(counter, "sharded", smoke.phase_sharded, tiny, trace, 4,
                    n_shards)


def _result(smoke, tiny):
    stage = smoke.make_stage(tiny, smoke.WordCount(), "columnar", 1)
    trace = smoke.make_trace(dataclasses.replace(tiny, intervals=3), 1)
    for keys in trace:
        stage.process_interval_arrays(keys)
    return smoke.stage_result(stage, np.arange(64))


def _mutate(field, r):
    if field == "theta":
        rep = dataclasses.replace(r.reports[-1],
                                  theta=r.reports[-1].theta + 1e-12)
        return dataclasses.replace(r, reports=r.reports[:-1] + [rep])
    if field == "outputs":
        k = next(iter(r.outputs))
        return dataclasses.replace(r, outputs={**r.outputs,
                                               k: r.outputs[k] + 1})
    if field == "emitted_sum":
        return dataclasses.replace(r, emitted_sum=r.emitted_sum + 1)
    if field == "locations":
        return dataclasses.replace(r, locations=r.locations[1:] + [[99]])
    t = next(i for i, p in enumerate(r.packs) if p.keys.size)
    pack = r.packs[t].clone()
    pack.vals[0, 0] += 1
    return dataclasses.replace(r, packs=r.packs[:t] + [pack]
                               + r.packs[t + 1:])


@pytest.mark.parametrize("field", ["theta", "outputs", "emitted_sum",
                                   "locations", "packs"])
def test_comparison_catches_a_one_field_difference(smoke, tiny, field):
    want = _result(smoke, tiny)
    smoke.same_stage(want, want, "self")
    with pytest.raises(smoke.SmokeFailure):
        smoke.same_stage(_mutate(field, want), want, "mutated")
