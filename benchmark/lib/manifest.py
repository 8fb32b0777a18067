"""Find a cell's files by the names in ``BENCHMARK.json``.  Adding a cell, a
configuration, a traffic mix or a per-layer metric is adding files and
entries; nothing here knows a name."""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


class ManifestError(Exception):
    pass


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"missing file: {os.path.relpath(path, ROOT)} "
                            f"({e.strerror})") from None


def benchmark_json():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name, manifest=None):
    """Everything one cell needs, resolved: the workload entry, its
    configuration (entry, file, profile template path), its traffic (file
    merged with the cell file's ``params``), its generator module, and its
    end-to-end and per-layer metric entries with each reader's file."""
    manifest = manifest or benchmark_json()
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in manifest['workloads']]})")
    cfg_entry = next((c for c in manifest["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise ManifestError(f"workload {name!r} names configuration "
                            f"{entry['config']!r}, which is not listed")
    config = _load(os.path.join(ROOT, cfg_entry["file"]))
    profile = os.path.join(ROOT, config["profile"])
    if not os.path.isfile(profile):
        raise ManifestError(f"missing file: {config['profile']}")
    traffic = _load(os.path.join(BENCH, "traffic",
                                 entry["traffic"] + ".json"))
    cell_path = os.path.join(BENCH, "workloads", name + ".json")
    cell_file = _load(cell_path) if os.path.isfile(cell_path) else {}
    params = {**traffic, **cell_file.get("params", {})}
    try:
        generator = importlib.import_module(
            "benchmark.generators." + params["generator"])
    except ImportError as e:
        raise ManifestError(f"traffic {entry['traffic']!r}: no generator "
                            f"{params['generator']!r} ({e})") from None

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    per_layer = []
    for m in manifest["per_layer"]:
        if mine(m):
            reader = _load(os.path.join(BENCH, "metrics",
                                        m["name"] + ".json"))
            per_layer.append({**m, "reader": reader})
    return {
        "entry": entry, "config_entry": cfg_entry, "config": config,
        "profile_template": profile, "params": params,
        "cell_file": cell_file, "generator": generator,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": per_layer,
    }
