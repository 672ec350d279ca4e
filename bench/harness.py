"""The benchmark harness: one run of one cell.

Everything that belongs to one cell, configuration, per-layer metric or
kernel sits in a file of its own under ``bench/`` and is found here by
the name ``BENCHMARK.json`` gives it:

  cells/<cell>.json       configuration, traffic parameters, chips, why
  configs/<config>.json   shape, workload, version, parameters, and the
                          names of its data generator, reference and work
  data/<name>.py          ``generate(seed, n, n_features, **data_params)``
  ref/<name>.py           ``fit(**data, **reference_params)``, ``compare``
  work/<config>.py        ``work(n, n_features, params)``: flops, bytes
  kernels/<kernel>.py     trace name and ``cost(n, n_features, params)``
  metrics/<metric>.py     ``read(run)``: the metric's value, or None

A run: set-up (generate the data from the seed, place it with
``make_system("pim").put``, materialise its view, one warm fit), then a
window of back-to-back fits through ``make_estimator(...).fit(ds)``,
then the comparison of the window's fits with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "bench.window"
FIT_SPAN = "bench.fit"
#: a traced run profiles this many seconds of its window (whole fits)
TRACE_SECONDS = 10.0


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, unknown device, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_plugin(kind: str, name: str, bench: Path = BENCH):
    """Import ``bench/<kind>/<name>.py`` by its path (names may hold dots)."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str, bench: Path = BENCH) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict        # cells/<cell>.json
    config: dict      # configs/<config>.json

    @property
    def n(self) -> int:
        return int(self.config["n_samples"])

    @property
    def n_features(self) -> int:
        return int(self.config["n_features"])

    def fit_params(self, seed: int) -> dict:
        params = dict(self.config["params"])
        params.update(self.spec.get("params", {}))
        if "seed_param" in self.config:
            params[self.config["seed_param"]] = seed % 2 ** 32
        return params

    def reference_params(self, seed: int) -> dict:
        params = dict(self.config["reference_params"])
        if "seed_param" in self.config:
            params["seed"] = seed % 2 ** 32
        return params


def resolve_cell(name: str, bench: Path = BENCH,
                 shape: Optional[tuple] = None) -> Cell:
    """The cell ``name`` with its configuration; ``shape`` replaces the
    configuration's (n_samples, n_features) for a run at test size."""
    spec = load_json(bench / "cells" / f"{name}.json")
    config = load_json(bench / "configs" / f"{spec['config']}.json")
    if shape is not None:
        config = dict(config, n_samples=shape[0], n_features=shape[1])
    return Cell(name, spec, config)


def cell_metrics(name: str, section: str, root: Path = ROOT) -> list:
    """The ``end_to_end`` or ``per_layer`` entries of BENCHMARK.json that
    this cell reports."""
    entries = load_json(root / "BENCHMARK.json")[section]
    return [m for m in entries if name in m.get("workloads", [name])]


@dataclasses.dataclass
class Run:
    """Everything one run measured; the metric readers read from it."""

    cell: Cell
    seed: int
    peaks: Optional[dict] = None
    setup: dict = dataclasses.field(default_factory=dict)
    fits: int = 0
    attempted: int = 0
    failed: int = 0
    window_s: float = 0.0
    traced_fits: int = 0                  # fits inside the traced span
    stats: Optional[dict] = None          # TransferStats delta, window
    compiles_in_window: int = 0
    trace: Any = None                     # bench.trace.Trace, --trace 1
    outputs: list = dataclasses.field(default_factory=list)

    @property
    def fit_s(self) -> float:
        return self.window_s / self.fits

    def work(self) -> dict:
        mod = load_plugin("work", self.cell.config["work"])
        return mod.work(self.cell.n, self.cell.n_features,
                        self.cell.fit_params(self.seed))

    def roofline_s(self, flops: float, nbytes: float) -> float:
        """Least time the chip could take for this work."""
        return max(flops / self.peaks["bf16_flops_per_s"],
                   nbytes / self.peaks["hbm_bytes_per_s"])

    def kernel_roofline(self, kernel: str) -> Optional[float]:
        """A kernel's share of its roofline in %: its calls' least time
        over the device time of its events; None where the trace holds
        none of them."""
        if self.trace is None:
            return None
        mod = load_plugin("kernels", kernel)
        calls, seconds = self.trace.kernel(mod.TRACE_NAME)
        if not calls or seconds <= 0:
            return None
        c = mod.cost(self.cell.n, self.cell.n_features,
                     self.cell.fit_params(self.seed))
        return 100.0 * calls * self.roofline_s(c["flops"], c["bytes"]) \
            / seconds


class CompileCounter:
    """Counts XLA compilations (including persistent-cache loads) while
    armed, through JAX's monitoring events."""

    def __init__(self):
        self.armed = False
        self.count = 0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program,
    however quick to compile, so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _estimator(cell: Cell, ds, params: dict):
    from repro.api import make_estimator
    return make_estimator(cell.config["workload"],
                          version=cell.config["version"],
                          system=ds.system, **params)


def _outputs(cell: Cell, est) -> dict:
    return {k: np.asarray(getattr(est, k)) for k in cell.config["outputs"]}


def setup(run: Run, log) -> tuple:
    """Generate, place, materialise the view, warm fit.  Returns the
    placed dataset and the generated data."""
    import jax
    from repro.systems import make_system
    cell = run.cell
    t = time.perf_counter()
    gen = load_plugin("data", cell.config["data"])
    data = gen.generate(run.seed, cell.n, cell.n_features,
                        **cell.config.get("data_params", {}))
    run.setup["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = make_system("pim").put(data["X"], data.get("y"))
    view = cell.config["view"]
    jax.block_until_ready(getattr(ds, view["method"])(*view["args"]))
    run.setup["place_s"] = time.perf_counter() - t
    t = time.perf_counter()
    _estimator(cell, ds, cell.fit_params(run.seed)).fit(ds)
    run.setup["warm_s"] = time.perf_counter() - t
    log(f"setup: generate {run.setup['generate_s']:.3f} s, place "
        f"{run.setup['place_s']:.3f} s, warm fit {run.setup['warm_s']:.3f} s")
    return ds, data


def window(run: Run, ds, seconds: float, counter: CompileCounter,
           trace_dir: Optional[str], log) -> None:
    """Fits back to back until ``seconds`` have passed, then the fit in
    flight finishes.  Every fit's outputs are kept for the comparison.
    With ``trace_dir`` the profiler records the window's first fits, up
    to ``TRACE_SECONDS``, inside the span ``bench.window``."""
    import jax
    cell = run.cell
    params = cell.fit_params(run.seed)
    before = ds.system.stats.snapshot()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        # made once the profiler runs: a span made before it records nothing
        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        span.__enter__()
    counter.armed = True
    t0 = time.perf_counter()
    while True:
        run.attempted += 1
        try:
            with jax.profiler.TraceAnnotation(FIT_SPAN):
                est = _estimator(cell, ds, params).fit(ds)
                run.outputs.append(_outputs(cell, est))
            run.fits += 1
        except Exception as exc:  # noqa: BLE001 - counted, fails the run
            run.failed += 1
            log(f"fit {run.attempted} failed: {exc!r}")
        elapsed = time.perf_counter() - t0
        if trace_dir is not None and not run.traced_fits and (
                elapsed >= min(seconds, TRACE_SECONDS)):
            span.__exit__(None, None, None)
            run.traced_fits = run.fits
            jax.profiler.stop_trace()
        if elapsed >= seconds:
            break
    t1 = time.perf_counter()
    counter.armed = False
    run.window_s = t1 - t0
    run.compiles_in_window = counter.count
    run.stats = dataclasses.asdict(ds.system.stats.delta(before))
    log(f"window: {run.fits} fits in {run.window_s:.3f} s, "
        f"{run.failed} failed, {counter.count} compiles"
        + (f", the first {run.traced_fits} traced" if trace_dir else ""))


def check(run: Run, data: dict, log) -> tuple[bool, dict]:
    """Compare the window's fits with the plain reference.

    Every fit of the window ran on the same data with the same
    parameters, so all must equal the first bit for bit (``fits_differ``,
    limit 0); one fit, drawn from the seed, is compared with the
    reference under the configuration's limits."""
    cell = run.cell
    checks = {}
    if not run.outputs:
        return False, {"fits_compared": {"value": 0, "limit": 1}}
    first = run.outputs[0]
    differ = sum(1 for out in run.outputs[1:]
                 if any(not np.array_equal(out[k], first[k]) for k in first))
    checks["fits_differ"] = {"value": differ, "limit": 0}
    pick = int(np.random.default_rng(run.seed).integers(len(run.outputs)))
    ref_mod = load_plugin("ref", cell.config["reference"])
    t = time.perf_counter()
    ref = ref_mod.fit(**data, **cell.reference_params(run.seed))
    log(f"reference: {time.perf_counter() - t:.3f} s, fit {pick + 1} of "
        f"{len(run.outputs)} compared")
    numbers = ref_mod.compare(run.outputs[pick], ref, **data)
    limits = cell.config["limits"]
    ok = differ == 0 and run.failed == 0
    for name, value in numbers.items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and math.isfinite(value) \
            and value <= limit
    return ok, checks


def device_info(run: Run, n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_metrics(run: Run, entries: list, bench: Path = BENCH) -> dict:
    """Per-layer metrics through their readers; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_plugin("metrics", m["name"], bench).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, shape: Optional[tuple] = None,
            keep_trace: Optional[str] = None, t_start: Optional[float] = None,
            limits: Optional[dict] = None,
            log=None) -> tuple[dict, list]:
    """One run.  Returns (result line, check lines).

    ``require_tpu=False``, ``shape`` and ``limits`` are for the tests:
    a run on the CPU at a test size, held to the test size's limits."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = resolve_cell(cell_name, shape=shape)
    if limits is not None:
        cell.config = dict(cell.config, limits=limits)
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform}")
    n_chips = int(cell.spec["chips"])
    if len(devs) < n_chips:
        raise BenchError(f"cell {cell_name} needs {n_chips} chips, JAX "
                         f"finds {len(devs)}")
    run = Run(cell, seed)
    if require_tpu or trace:
        run.peaks = peaks_for(devs[0].device_kind)
    cache = enable_compile_cache() if require_tpu else "off"
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache}")
    counter = CompileCounter()
    ds, data = setup(run, log)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        window(run, ds, seconds, counter, trace_dir, log)
        device = device_info(run, n_chips)
        if trace:
            from bench import trace as xtrace
            run.trace = xtrace.load(trace_dir, WINDOW_SPAN)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(run.trace.path, os.path.join(
                    keep_trace, f"{cell_name}.xplane.pb"))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs on the chip
    del ds
    gc.collect()
    correct, checks = check(run, data, log)
    del data

    result: dict = {"correct": correct, "attempted": run.attempted,
                    "failed": run.failed}
    if trace:
        result["metrics"] = read_metrics(
            run, cell_metrics(cell_name, "per_layer"))
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["device"] = device
        result["breakdown"] = run.trace.breakdown()
    else:
        values = {"setup_s": setup_s,
                  "fit_s": run.fit_s if run.fits else float("nan")}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(cell_name, "end_to_end")}
        result["device"] = device
    result["checks"] = checks
    lines = [f"check {k} = {v['value']!r} (limit {v['limit']!r})"
             for k, v in checks.items()]
    return result, lines
