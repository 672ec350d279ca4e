"""The benchmark under bench/: names resolve, required work and kernel
bytes, the peaks table, and the refusal to run without a TPU.  CPU only."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
SUSY, HIGGS = (5_000_000, 18), (11_000_000, 28)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file_by_name(cell):
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    c = harness.resolve_cell(cell)
    assert c.spec["config"] == entry["config"]
    assert c.spec["traffic"] == entry["traffic"]
    assert c.spec["chips"] == entry["chips"]
    assert c.spec["why"] == entry["why"]
    conf = next(x for x in BENCHMARK["configs"] if x["name"] == entry["config"])
    assert (ROOT / conf["file"]).resolve() == (
        harness.BENCH / "configs" / f"{entry['config']}.json")
    assert callable(harness.load_plugin("data", c.config["data"]).generate)
    ref = harness.load_plugin("ref", c.config["reference"])
    assert callable(ref.fit) and callable(ref.compare)
    assert callable(harness.load_plugin("work", c.config["work"]).work)
    for k in c.config["kernels"]:
        mod = harness.load_plugin("kernels", k)
        assert mod.TRACE_NAME and callable(mod.cost)
    for section in ("end_to_end", "per_layer"):
        for m in harness.cell_metrics(cell, section):
            if section == "per_layer":
                assert callable(harness.load_plugin("metrics", m["name"]).read)
    names = {m["name"] for m in harness.cell_metrics(cell, "end_to_end")}
    assert {"fit_s", "setup_s"} <= names
    assert harness.cell_metrics(cell, "per_layer")
    limits = c.config["limits"]
    assert limits and all(v is not None and v > 0 for v in limits.values())


def test_every_configuration_and_metric_is_used():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    for m in BENCHMARK["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_new_cell_config_and_metric_need_only_new_files(tmp_path):
    """A later cell, configuration and per-layer metric are new files and
    new BENCHMARK.json entries: nothing that exists is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = json.loads((bench / "configs" / "log_lut_susy.json").read_text())
    conf.update(name="log_lut_skin", n_samples=245_057, n_features=3,
                work="log_lut_skin")
    (bench / "configs" / "log_lut_skin.json").write_text(json.dumps(conf))
    (bench / "work" / "log_lut_skin.py").write_text(
        "def work(n, n_features, params):\n"
        "    return {'flops': 0, 'bytes': n * n_features * 4}\n")
    (bench / "cells" / "log_lut_skin.serial.json").write_text(json.dumps(
        {"config": "log_lut_skin", "traffic": "serial", "chips": 1,
         "params": {"fuse_steps": 1}, "why": "a smaller table"}))
    (bench / "metrics" / "fits_per_window.py").write_text(
        "def read(run):\n    return run.fits\n")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "log_lut_skin.serial",
                            "config": "log_lut_skin", "traffic": "serial",
                            "chips": 1, "why": "a smaller table"})
    bm["per_layer"].append({"name": "fits_per_window", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "whole fit", "moves": "fit_s",
                            "workloads": ["log_lut_skin.serial"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.resolve_cell("log_lut_skin.serial", bench=bench)
    assert cell.n == 245_057
    work = harness.load_plugin("work", cell.config["work"], bench=bench)
    assert work.work(cell.n, cell.n_features, {})["bytes"] == 245_057 * 12
    per_layer = harness.cell_metrics("log_lut_skin.serial", "per_layer",
                                     root=tmp_path)
    assert "fits_per_window" in {m["name"] for m in per_layer}
    run = harness.Run(cell, seed=1, fits=7)
    assert harness.read_metrics(
        run, [m for m in per_layer if m["name"] == "fits_per_window"],
        bench=bench) == {"fits_per_window": {"value": 7, "unit": "count"}}
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("config,shape,expected", [
    ("log_lut_susy", SUSY, 100 * (5_000_000 * 18 * 4 + 5_000_000 * 4)),
    ("kme_int16_higgs", HIGGS, 11 * 11_000_000 * 28 * 2 + 11_000_000 * 4),
])
def test_required_work_bytes_at_the_cells_shapes(config, shape, expected):
    conf = json.loads((harness.BENCH / "configs" / f"{config}.json")
                      .read_text())
    assert (conf["n_samples"], conf["n_features"]) == shape
    w = harness.load_plugin("work", conf["work"]).work(
        *shape, conf["params"])
    assert w["bytes"] == expected
    peaks = harness.peaks_for("TPU v5 lite")
    # both fits are bound by bytes on v5e: 46 ms (LOG) and 8.3 ms (KME)
    assert w["flops"] / peaks["bf16_flops_per_s"] < \
        w["bytes"] / peaks["hbm_bytes_per_s"]


@pytest.mark.parametrize("kernel,shape,params,nbytes,flops", [
    ("fx_matvec", SUSY, {}, 5_000_000 * 18 * 4 + 5_000_000 * 4, 0),
    ("lut_sigmoid", SUSY, {}, 2 * 5_000_000 * 4 + 2 * 20_480, 0),
    ("kmeans_assign", HIGGS, {"n_clusters": 16},
     11_000_000 * 28 * 2 + 11_000_000 * 4, 4 * 11_000_000 * 16 * 28),
])
def test_kernel_cost_at_the_cells_shapes(kernel, shape, params, nbytes,
                                         flops):
    c = harness.load_plugin("kernels", kernel).cost(*shape, params)
    assert c == {"flops": flops, "bytes": nbytes}


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    table = json.loads((harness.BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    p = harness.peaks_for("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                 "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks_for("cpu")


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "log_lut_susy.serial",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCHMARK["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
