"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """Import ``<benchmarks>/<folder>/<name>.py``; names may hold dots."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its names point to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]


def _reported_by(metrics: list[dict], cell: str, e2e_of_cell: set | None) -> list[str]:
    out = []
    for m in metrics:
        listed = m.get("workloads")
        if listed is not None:
            hit = cell in listed
        else:  # no list: every cell that reports the metric it moves
            hit = e2e_of_cell is None or m["moves"] in e2e_of_cell
        if hit:
            out.append(m["name"])
    return out


def load_cell(name: str, *, overrides: dict | None = None) -> Cell:
    """``overrides`` (tests only) replaces the configuration or traffic file
    with a dict: ``{"config": {...}, "traffic": {...}}``."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    overrides = overrides or {}
    config = overrides.get("config") or _load_json(
        os.path.join(ROOT, cfg_entry["file"])
    )
    traffic = overrides.get("traffic") or _load_json(
        os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json")
    )
    e2e = _reported_by(bench["end_to_end"], name, None)
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=config, traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=e2e,
        per_layer=_reported_by(bench["per_layer"], name, set(e2e)),
    )
