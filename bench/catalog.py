"""Finds the benchmark's data by name: a cell in ``BENCHMARK.json``, its
configuration in ``configs/<name>.json``, the configuration's family (its
weights, plain reference and operation counts) in
``families/<family>.py``, its traffic mix in ``traffic/<name>.json``, its
limits in ``limits/<cell>.json`` and each per-layer metric's reader in
``metrics/<name>.py``.  A new cell, mix, configuration, family or metric
is a new file; nothing here changes."""
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
    """The sizes of ``configs/<name>.json``; a configuration that names
    its family (``"family"``) gets the path of that family's file beside
    it under ``"family_file"``."""
    sz = _load_json(bench_dir / "configs" / f"{name}.json")
    if "family" in sz:
        sz["family_file"] = str(
            (bench_dir / "families" / f"{sz['family']}.py").resolve())
    return sz


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "traffic" / f"{name}.json")


def limits(cell_name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "limits" / f"{cell_name}.json")


def peaks(bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "peaks.json")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _module(bench_dir / "metrics" / f"{name}.py",
                   f"bench_metric_{name.replace('.', '_')}").read


#: each family file loaded, by its path
_loaded: dict = {}


def family(sz: dict):
    """The module of a configuration's family: the file ``config`` found
    for it (``"family_file"``), else ``families/<family>.py`` of this
    package, ``dense`` where the configuration names no family."""
    path = sz.get("family_file") or str(
        BENCH_DIR / "families" / f"{sz.get('family', 'dense')}.py")
    if path not in _loaded:
        name = Path(path).stem.replace(".", "_").replace("-", "_")
        _loaded[path] = _module(Path(path), f"bench_family_{name}")
    return _loaded[path]


def metrics_of(cell_name: str, kind: str, checkout: Path = CHECKOUT
               ) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in benchmark(checkout)[kind]
            if cell_name in m.get("workloads", [cell_name])]
