"""One run of one benchmark cell.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<config>.json`` (which names a pipeline in
``pipelines/<pipeline>.py`` and its plain reference in
``pipelines/<pipeline>.reference.py``), its traffic in
``traffic/<mix>.json``, drawn by ``source.py`` or, where the configuration
names a ``traffic_source``, by ``sources/<traffic_source>.py``, each
metric's reader in ``metrics/<metric>.py`` (or, for a metric
``<name>.<suffix>`` split by the end-to-end metric it moves, the shared
``metrics/<name>.py``), each kernel's work function in
``kernels/<kernel>.py`` and the chip's peaks in ``peaks.json``. A run:

1. refuses a machine whose first device is not a TPU of a known kind, or
   with fewer chips than the cell asks for;
2. turns on the persistent compilation cache and builds the system;
3. draws the traffic from the seed, runs the warm-up intervals and
   compiles every route shape the window can reach (set-up ends here);
4. runs the window: the next interval is handed over as soon as the last
   one returned (the source is always ahead), until ``seconds`` have
   passed;
5. reads the program's answers, frees it, and compares them with the plain
   reference; prints the numbers compared, each beside its limit, and the
   result line.

A traced run also hands the readers the device trace's summary
(``tracereduce``), the program spans' self times (``spanreduce``) and the
change of the program's counters over the window.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = ("source", "interval")   # the harness's host spans in a trace


class Refused(Exception):
    """The machine cannot run the cell; no result is printed."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with its configuration and metrics."""

    name: str
    chips: int
    config: dict
    traffic: str
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, name: str, bench_file: Path = ROOT / "BENCHMARK.json"
             ) -> "Cell":
        spec = json.loads(bench_file.read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
        w = cells[name]
        cfg = json.loads((BENCH / "configs" / f"{w['config']}.json")
                         .read_text())
        return cls(name, int(w["chips"]), cfg, w["traffic"],
                   [m for m in spec["end_to_end"] if applies(m, name)],
                   [m for m in spec["per_layer"] if applies(m, name)])


def check_device(devices: Sequence, chips: int, peaks: dict) -> dict:
    """The peaks of the run's chip; raises :class:`Refused` unless the
    first device is a TPU of a kind the peaks table holds and there are
    ``chips`` of them."""
    first = devices[0]
    if first.platform != "tpu":
        raise Refused(f"JAX's first device is {first.platform!r}, not a TPU")
    if len(devices) < chips:
        raise Refused(f"{len(devices)} devices, the cell needs {chips}")
    kind = first.device_kind
    if kind not in peaks["devices"]:
        raise Refused(f"no peaks for device kind {kind!r}; known: "
                      f"{sorted(peaks['devices'])}")
    return peaks["devices"][kind]


class CompileCounter:
    """Counts the programs JAX compiled or loaded from the persistent cache
    (one backend-compile event each) and, of them, the cache loads."""

    def __init__(self):
        import jax
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Interval:
    """One interval of the window, on the host clock (seconds)."""

    tuples: int
    due: float
    handed: float = math.nan
    done: float = math.nan

    @property
    def completed(self) -> bool:
        return not math.isnan(self.done)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    mix: object
    setup_s: float
    t0: float
    t_close: float
    intervals: List[Interval]
    plan_s: float = 0.0
    summary: object = None          # tracereduce.Summary of a traced run
    spans: object = None            # spanreduce.SpanSummary of a traced run
    counters: Optional[Dict[str, int]] = None   # change over the window
    kernel_shapes: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t0

    def done(self) -> List[Interval]:
        return [iv for iv in self.intervals if iv.completed]

    def kernel_work(self, kernel: str) -> Callable[[dict], float]:
        return load_module(BENCH / "kernels" / f"{kernel}.py",
                           f"kernel_{kernel}").hbm_bytes


def reader_path(name: str) -> Path:
    """``metrics/<name>.py``, else the shared reader of ``<name>`` without
    its last dotted suffix."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        reader = load_module(reader_path(m["name"]), f"metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_window(system, pool: Sequence[np.ndarray], seconds: float,
               span: Callable) -> tuple:
    """The measured window; returns ``(t0, t_close, intervals, error)``."""
    intervals: List[Interval] = []
    error = None
    t0 = time.perf_counter()
    for keys in pool:
        h = time.perf_counter()
        iv = Interval(int(keys.size), due=h, handed=h)
        intervals.append(iv)
        try:
            with span("interval"):
                system.process(keys)
        except Exception:                  # the run goes on to report it
            error = traceback.format_exc()
            break
        iv.done = time.perf_counter()
        if iv.done - t0 >= seconds:
            break
    else:
        print(f"note: the traffic pool ran out after {len(pool)} "
              "intervals, before the window's length", file=sys.stderr)
    t_close = max((iv.done for iv in intervals if iv.completed),
                  default=time.perf_counter())
    return t0, t_close, intervals, error


def traffic_source(cfg: dict):
    """The module that draws the configuration's traffic: ``source`` or
    ``sources/<traffic_source>.py``."""
    if "traffic_source" not in cfg:
        import source
        return source
    name = cfg["traffic_source"]
    return load_module(BENCH / "sources" / f"{name}.py", f"source_{name}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             control: bool = False,
             bench_file: Path = ROOT / "BENCHMARK.json") -> Tuple[dict, Run]:
    """One run of cell ``name``; returns the result line's object and what
    its metrics were read from.

    ``overrides``/``traffic_overrides`` replace configuration and traffic
    values (the tests shrink a cell with them). ``require_tpu=False``
    lets a test run on the CPU, without the compile cache and without the
    peaks a roofline needs. ``control`` also compares the control (the
    reference's own answers in a narrower arithmetic, put in the program's
    place) and returns its numbers under ``"control"``. ``bench_file``
    is where the cell is defined."""
    import jax
    from repro.core.telemetry import COUNTERS, SPANS as PROGRAM_SPANS

    cell = Cell.load(name, bench_file)
    cfg = dict(cell.config, **(overrides or {}))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    devices = jax.devices()
    chip = check_device(devices, cell.chips, peaks) if require_tpu else {}

    if require_tpu:
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        # every program goes to the cache, however quickly it compiled
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()

    import source
    mix = source.Mix.load(cell.traffic)
    if traffic_overrides:
        mix = dataclasses.replace(mix, **traffic_overrides)
    n_window = mix.window_intervals(seconds, cfg["tuples"])
    stream = traffic_source(cfg).traffic(
        cfg, mix, mix.warmup_intervals + n_window, seed)
    warmup, pool = (stream[:mix.warmup_intervals],
                    stream[mix.warmup_intervals:])

    pipeline = load_module(BENCH / "pipelines" / f"{cfg['pipeline']}.py",
                           f"pipeline_{cfg['pipeline']}")
    interpret = jax.default_backend() != "tpu"
    system = pipeline.System(cfg, interpret)
    for keys in warmup:
        system.process(keys)
    system.warm(max(int(k.max()) for k in stream))
    warm_programs = counter.programs

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda name: contextlib.nullcontext()  # noqa: E731
    c0 = counter.programs
    setup_s = time.perf_counter() - t_start
    before = dict(COUNTERS)
    with span("window"):
        t0, t_close, intervals, error = run_window(system, pool, seconds,
                                                   span)
    in_window = counter.programs - c0
    counters = {k: v - before.get(k, 0) for k, v in COUNTERS.items()}
    if trace:
        jax.profiler.stop_trace()
    print(f"compiles in the window: {in_window} (set-up: {warm_programs} "
          f"programs compiled or loaded, {counter.cache_hits} of all loaded "
          "from the persistent cache)", flush=True)

    n_before = mix.warmup_intervals
    n_done = sum(iv.completed for iv in intervals)
    run = Run(cell, mix, setup_s, t0, t_close, intervals,
              plan_s=system.plan_seconds(n_before + 1, n_before + n_done),
              counters=counters, kernel_shapes=system.kernel_shapes(),
              peaks=chip)
    memory_peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                        0)
    observed = system.observe() if error is None else None
    print("window intervals, ms taken/late: " + " ".join(
        f"{1e3 * (iv.done - iv.handed):.0f}/{1e3 * (iv.handed - iv.due):.0f}"
        for iv in intervals), flush=True)
    if observed is not None:
        print("plans at intervals: " + "; ".join(
            f"{k} {[p['interval'] for p in v['plans']]}"
            for k, v in observed.items()), flush=True)
    del system
    gc.collect()

    checks: Dict[str, float] = {}
    control_checks: Dict[str, float] = {}
    if observed is not None:
        ref = load_module(BENCH / "pipelines"
                          / f"{cfg['pipeline']}.reference.py",
                          f"reference_{cfg['pipeline']}")
        processed = warmup + pool[:n_done]
        checks = ref.check(cfg, processed, observed)
        if control:
            control_checks = ref.check(cfg, processed,
                                       ref.control(cfg, processed, observed))
    attempted = sum(iv.tuples for iv in intervals)
    failed = attempted - sum(iv.tuples for iv in intervals if iv.completed)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result: dict = {}
    if trace:
        from jax.profiler import ProfileData
        import spanreduce
        import tracereduce
        files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
        if files:
            profile = ProfileData.from_file(str(files[-1]))
            run.summary = tracereduce.reduce(profile, SPANS, PROGRAM_SPANS)
            run.spans = spanreduce.reduce(profile, PROGRAM_SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = read_metrics(run, cell.per_layer)
        if run.summary is not None:
            device["busy_s"] = run.summary.busy_s
            device["window_s"] = run.summary.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in run.summary.ops],
                "idle_gaps": [[n, s] for n, s in run.summary.idle_gaps]}
    else:
        metrics = read_metrics(run, cell.end_to_end)

    # every number compared is a count of disagreements or an absolute
    # gap, and must be exactly 0
    limits = {k: 0.0 for k in checks}
    correct = (error is None and failed == 0 and bool(checks)
               and all(checks[k] <= limits[k] for k in checks))
    if error is not None:
        print(error, file=sys.stderr)
    for k in checks:
        print(f"check {k}: {checks[k]} (limit {limits[k]})", file=sys.stderr)
    if control:
        result["control"] = control_checks
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device,
              **result,
              "checks": {k: {"value": checks[k], "limit": limits[k]}
                         for k in checks}}
    return result, run
