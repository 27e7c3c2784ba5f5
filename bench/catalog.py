"""Finds the benchmark's data by name: a cell in ``BENCHMARK.json``, its
configuration in ``configs/<name>.json``, its traffic mix in
``traffic/<name>.json``, its limits in ``limits/<cell>.json`` and each
per-layer metric's reader in ``metrics/<name>.py``.  A new cell, mix,
configuration or metric is a new file; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: Path = CHECKOUT) -> dict:
    return _load_json(checkout / "BENCHMARK.json")


def cell(name: str, checkout: Path = CHECKOUT) -> dict:
    for w in benchmark(checkout)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "traffic" / f"{name}.json")


def limits(cell_name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "limits" / f"{cell_name}.json")


def peaks(bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "peaks.json")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(cell_name: str, kind: str, checkout: Path = CHECKOUT
               ) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in benchmark(checkout)[kind]
            if cell_name in m.get("workloads", [cell_name])]
