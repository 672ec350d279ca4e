"""repro.obs — the unified telemetry layer (DESIGN.md §13).

Observability for the whole runtime, on nothing but the standard
library and ``jax.profiler``:

  :mod:`repro.obs.trace`         span tracer (ring buffer and profiler
                                 trace, global TRACER)
  :mod:`repro.obs.chrome_trace`  Chrome trace-event JSON export
  :mod:`repro.obs.metrics`       counters / gauges / histograms registry
  :mod:`repro.obs.format`        shared CLI table rendering
  :mod:`repro.obs.runmeta`       provenance envelope for persisted JSON

Environment hook: setting ``REPRO_TRACE=/path/to/trace.json`` enables
the global tracer at import time and registers an atexit export of the
buffer to that path — any entry point (CLI, pytest, notebook) becomes
traceable without code changes.
"""
from __future__ import annotations

import atexit
import os

from repro.obs.chrome_trace import (load_chrome_trace, to_chrome_trace,
                                    track_names, validate_chrome_trace,
                                    write_chrome_trace)
from repro.obs.format import Column, format_bytes, format_ratio, render_table
from repro.obs.metrics import (DRIFT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.runmeta import run_meta, write_json
from repro.obs.trace import TRACER, Tracer

__all__ = [
    "TRACER", "Tracer",
    "to_chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "load_chrome_trace", "track_names",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "DRIFT_BUCKETS",
    "Column", "render_table", "format_bytes", "format_ratio",
    "run_meta", "write_json",
]


def _install_env_trace() -> None:
    path = os.environ.get("REPRO_TRACE")
    if not path:
        return
    TRACER.enable()

    def _export() -> None:
        events = TRACER.events()
        if events:
            write_chrome_trace(events, path)

    atexit.register(_export)


_install_env_trace()
