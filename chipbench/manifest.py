"""Finds a cell's parts by name.

BENCHMARK.json names each cell, its configuration, its traffic mix and
the metrics it reports. The parts themselves are files found by those
names, so a later change adds a cell, a configuration, a mix, a client
kind or a metric by adding files and manifest entries only:

  configs/<config>.json     model sizes as run, with source and cuts
  traffic/<traffic>.json    parameters the general generator reads
  workloads/<cell>.json     the cell's correctness limit and sample size
  clients/<kind>.py         a client kind (`make(traffic)`)
  metrics/<metric>.py       one metric's reader (`read(run)`)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent


class ManifestError(RuntimeError):
    """A name that BENCHMARK.json or a cell file gives has no file."""


def load_manifest(repo: Path = REPO) -> dict:
    path = Path(repo) / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def cell_entry(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"BENCHMARK.json has no workload {name!r}; it has "
                        f"{[w['name'] for w in manifest['workloads']]}")


def load_json(kind: str, name: str, root: Path = ROOT) -> dict:
    path = Path(root) / kind / f"{name}.json"
    if not path.is_file():
        raise ManifestError(f"no {kind} file for {name!r} at {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, root: Path = ROOT):
    path = Path(root) / kind / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no {kind} module for {name!r} at {path}")
    mod_name = "chipbench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(manifest: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics with
    --trace 0, its per-layer metrics with --trace 1."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def resolve(cell: str, trace: bool, repo: Path = REPO,
            root: Path = ROOT) -> Dict[str, object]:
    """Everything a run of `cell` needs, loaded by name: its manifest
    entry, configuration, traffic, cell file, client module and metric
    readers. Raises ManifestError naming the first part not found."""
    manifest = load_manifest(repo)
    entry = cell_entry(manifest, cell)
    traffic = load_json("traffic", entry["traffic"], root)
    metrics = metrics_for(manifest, cell, trace)
    return {
        "entry": entry,
        "config": load_json("configs", entry["config"], root),
        "traffic": traffic,
        "cell": load_json("workloads", cell, root),
        "client": load_module("clients", traffic["client"], root),
        "metrics": [(m, load_module("metrics", m["name"], root))
                    for m in metrics],
    }
