"""Runs one cell once: set-up, one measured window, the check, one line.

Everything a cell needs is found by name (see ``bench/README.md``):
``BENCHMARK.json`` names the cell's configuration and traffic; the
configuration's file and ``bench/traffic/<traffic>.json`` hold the
data; the traffic file names its driver, ``bench/drivers/<driver>.py``;
each per-layer metric is ``bench/metrics/<metric>.json``, which names
its reducer, ``bench/reducers/<reducer>.py``.

A driver module provides

    setup(ctx) -> state                      inputs and warm-up
    window(state, ctx, t0) -> WindowResult   the measured loop
    check(state, result) -> {name: (value, limit)}

and a reducer module ``read(metric, obs) -> float | None``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

from . import grids

__all__ = ["Context", "WindowResult", "Suite", "main", "make_scheduler", "run_cell"]

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".jax_cache"
ANNOTATIONS = ("probe", "place_batch", "snapshot", "complete", "reset", "sleep",
               "sim_run", "trace_start")


@dataclass
class WindowResult:
    """What a driver's window produced. ``end_to_end`` holds the values
    of the end-to-end metrics it measures; ``series`` host-clock series
    the reducers read (seconds)."""

    end_to_end: dict[str, float]
    attempted: int
    failed: int
    series: dict[str, list] = field(default_factory=dict)
    record: object = None


@dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    grid: grids.Grid
    annotate: Callable[[str], contextlib.AbstractContextManager]
    scheduler_factory: Optional[Callable] = None


class Suite:
    """The benchmark's files under one root, looked up by name."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, *parts: str) -> dict:
        return json.loads(self.bench.joinpath(*parts).read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config named {name!r}")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def driver(self, name: str) -> ModuleType:
        return _load(self.bench / "drivers" / f"{name}.py")

    def reducer(self, name: str) -> ModuleType:
        return _load(self.bench / "reducers" / f"{name}.py")

    def metric(self, name: str) -> dict:
        return self._json("metrics", f"{name}.json")

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [
            m for m in self.spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved else [])
        ]


def make_scheduler(ctx: Context):
    """The system under test: a ``DianaScheduler`` over fresh copies of
    the grid's sites, links and topology (or, in the tests, whatever
    ``ctx.scheduler_factory`` builds from them)."""
    sites, links, topology = ctx.grid.scheduler_inputs()
    if ctx.scheduler_factory is not None:
        return ctx.scheduler_factory(sites, links, topology)
    from repro.core import DianaScheduler

    return DianaScheduler(sites, links, topology=topology)


def _load(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class DeviceProbe:
    """One call of the program's only device path, the §IV Pallas
    kernel (``PlacementEngine.cost_matrix(backend="kernel")``), over the
    grid and a fixed block of jobs. No decision path of the program runs
    on the device, and a traced run has to show device work: so a traced
    run compiles this call in set-up and makes it at the start and at
    the end of its window, and the device's idle share is read against
    it. Its output decides nothing. Untraced runs, which give the
    end-to-end metrics, neither compile nor make it."""

    JOBS = 256

    def __init__(self, ctx_grid: grids.Grid, config: dict, seed: int):
        from repro.core import PlacementEngine, SitePack

        sites, links, _ = ctx_grid.scheduler_inputs()
        self.sp = SitePack.from_scheduler(sites, links)
        d = grids.demands(config, self.JOBS, grids.rng_for(seed, 9))
        self.engine = PlacementEngine()
        self.jp = self.engine.pack_jobs(d.jobs())
        self()

    def __call__(self) -> None:
        self.engine.cost_matrix(self.jp, self.sp, backend="kernel")


def _device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _configure_jax(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(
    suite: Suite,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    scheduler_factory=None,
    traffic_override: Optional[dict] = None,
    keep_trace: Optional[str] = None,
) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_tpu=False`` and ``scheduler_factory`` exist for the
    tests, which drive a run on the CPU and put a broken scheduler in
    the program's place; ``traffic_override`` for the
    rate sweep, and ``keep_trace`` (a path) to save the collected trace
    events as JSON."""
    import jax

    cell = suite.cell(workload)
    dev = jax.devices()[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"bench: needs a TPU; JAX found {dev.platform!r}")
        if len(jax.devices()) < cell["chips"]:
            raise SystemExit(f"bench: {workload} needs {cell['chips']} chips; "
                             f"JAX found {len(jax.devices())}")
        peaks = json.loads((suite.bench / "peaks.json").read_text())
        if dev.device_kind not in peaks:
            raise SystemExit(f"bench: {dev.device_kind!r} is not in bench/peaks.json")

    config = suite.config(cell["config"])
    traffic = dict(suite.traffic(cell["traffic"]), **(traffic_override or {}))
    driver = suite.driver(traffic["driver"])
    grid = grids.make_grid(config, seed)
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=seed, seconds=seconds,
        grid=grid, annotate=jax.profiler.TraceAnnotation,
        scheduler_factory=scheduler_factory,
    )
    probe = DeviceProbe(grid, config, seed) if trace else None
    state = driver.setup(ctx)
    # What set-up made lives through the window: keep the collector
    # from walking it again and again (a full collection over a million
    # generated jobs stalls the window for a tenth of a second).
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    sampler = None
    trace_dir = None
    if trace:
        from .sampler import StackSampler

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        sampler = StackSampler().__enter__()
    with jax.profiler.TraceAnnotation("window"):
        if probe is not None:
            with jax.profiler.TraceAnnotation("probe"):
                probe()
        t0 = time.perf_counter()
        result = driver.window(state, ctx, t0)
        if probe is not None:
            with jax.profiler.TraceAnnotation("probe"):
                probe()
    if trace:
        sampler.__exit__(None, None, None)
        jax.profiler.stop_trace()
    device = _device_info(jax)

    breakdown = None
    metrics: dict[str, dict] = {}
    if trace:
        from . import tracing

        raw = tracing.collect(trace_dir, ANNOTATIONS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if keep_trace and raw:
            Path(keep_trace).write_text(json.dumps(raw))
        reduced = tracing.reduce(raw) if raw else None
        if not reduced or reduced["busy_s"] <= 0:
            raise SystemExit("bench: no device op in the traced window; "
                             + tracing.describe(raw))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        obs = Observations(
            samples=sampler.samples, trace=reduced, series=result.series,
            layer_maps={
                m["name"]: suite.metric(m["name"]).get("functions", [])
                for m in suite.per_layer(workload)
                if suite.metric(m["name"])["reducer"] == "host_share"
            },
        )
        for m in suite.per_layer(workload):
            spec = suite.metric(m["name"])
            value = suite.reducer(spec["reducer"]).read(dict(spec, name=m["name"]), obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in suite.end_to_end(workload):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in result.end_to_end:
                metrics[m["name"]] = {"value": result.end_to_end[m["name"]], "unit": m["unit"]}

    gc.unfreeze()
    checks = driver.check(state, result)
    line = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": result.attempted,
        "failed": result.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return line


@dataclass
class Observations:
    """What the reducers read: host stack samples of the window, the
    reduced device trace, the drivers' host-clock series, and the layer
    maps of the cell's host-share metrics."""

    samples: list
    trace: Optional[dict]
    series: dict
    layer_maps: dict[str, list[str]]


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    # The run writes only inside its checkout and the temporary
    # directory: the compile cache here (for JAX and for any program
    # code that reads the variable), the TPU runtime's logs there.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax

    _configure_jax(jax)
    line = run_cell(Suite(), args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=t_start)
    print(f"correct: {line['correct']}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
