"""Finds a cell's parts by name.

BENCHMARK.json names each cell, its configuration, its traffic mix and
the metrics it reports. The parts themselves are files found by those
names, so a later change adds a cell, a configuration, a model family,
a mix, a client kind or a metric by adding files and manifest entries
only:

  configs/<config>.json     model sizes as run, with source and cuts, and
                            the name of the model's family
  families/<family>.py      all that depends on the model's shape: its
                            widths, ModelConfig, weights' layout,
                            reference layers and counts (model.py)
  traffic/<traffic>.json    parameters the general generator reads
  workloads/<cell>.json     the cell's correctness limit and sample size
  clients/<kind>.py         a client kind (`make(traffic)`)
  metrics/<metric>.py       one metric's reader (`read(run)`)
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
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
    """The module at <root>/<kind>/<name>.py, loaded once per process
    and registered in sys.modules (a family's record finds its family
    there by its type's module)."""
    path = (Path(root) / kind / f"{name}.py").resolve()
    if not path.is_file():
        raise ManifestError(f"no {kind} module for {name!r} at {path}")
    mod_name = "chipbench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name) + "_" + hashlib.sha1(
            str(path).encode()).hexdigest()[:8]
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(mod_name, None)
        raise
    return mod


def load_family(conf: dict, root: Path = ROOT):
    """The family module that a configuration names."""
    if "family" not in conf:
        raise ManifestError(f"configuration {conf.get('name')!r} names no "
                            f"family")
    return load_module("families", conf["family"], root)


def metrics_for(manifest: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics with
    --trace 0, its per-layer metrics with --trace 1."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def resolve(cell: str, trace: bool, repo: Path = REPO,
            root: Path = ROOT) -> Dict[str, object]:
    """Everything a run of `cell` needs, loaded by name: its manifest
    entry, configuration, family module, traffic, cell file, client module
    and metric readers. Raises ManifestError naming the first part not
    found."""
    manifest = load_manifest(repo)
    entry = cell_entry(manifest, cell)
    config = load_json("configs", entry["config"], root)
    traffic = load_json("traffic", entry["traffic"], root)
    metrics = metrics_for(manifest, cell, trace)
    return {
        "entry": entry,
        "config": config,
        "family": load_family(config, root),
        "traffic": traffic,
        "cell": load_json("workloads", cell, root),
        "client": load_module("clients", traffic["client"], root),
        "metrics": [(m, load_module("metrics", m["name"], root))
                    for m in metrics],
    }
