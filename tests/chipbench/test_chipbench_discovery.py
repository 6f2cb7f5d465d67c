"""The chip benchmark finds every part of a cell by name, the model family
of every configuration among them, and a cell or a metric added as files
and manifest entries only is picked up."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import manifest  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_resolves(cell, trace):
    parts = manifest.resolve(cell, trace)
    assert parts["entry"]["name"] == cell
    assert parts["config"]["name"] == parts["entry"]["config"]
    assert callable(parts["client"].make)
    names = [m["name"] for m, _ in parts["metrics"]]
    assert names, "a cell reports at least one metric of each kind"
    assert all(callable(mod.read) for _, mod in parts["metrics"])
    if not trace:
        assert "setup_s" in names


def test_config_files_are_the_manifest_files():
    for c in MANIFEST["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_configs_family_resolves(config):
    """A configuration names its family, whose module gives the record
    of widths, and the record finds its way back to that module."""
    conf = manifest.load_json("configs", config)
    fam = manifest.load_family(conf)
    d = fam.dims(conf)
    assert d.family is fam and d.name == config
    for part in ("model_config", "shared_leaves", "stacks", "layer_leaves",
                 "layer_kind", "block", "head", "decode_flops",
                 "prefill_flops", "kernel_layers"):
        assert callable(getattr(fam, part)), part


def test_a_missing_family_is_named(tmp_path):
    conf = manifest.load_json("configs", MANIFEST["configs"][0]["name"])
    with pytest.raises(manifest.ManifestError, match="names no family"):
        manifest.load_family({k: v for k, v in conf.items()
                              if k != "family"})
    with pytest.raises(manifest.ManifestError, match="no_such_family"):
        manifest.load_family(dict(conf, family="no_such_family"))


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    root = tmp_path / "chipbench"
    (root / "traffic" / "throwaway_mix.json").write_text(json.dumps(
        {"client": "closed_loop", "pattern": "sporadic", "clients": 2,
         "deck": 2, "prompt_len": {"values": [64], "weights": [1.0]},
         "output_len": {"median": 8, "sigma": 0.1, "min": 4, "max": 16},
         "think_s": {"mean": 0.0}}))
    (root / "workloads" / "throwaway.cell.json").write_text(json.dumps(
        {"check": {"requests": 1, "min_tokens": 1, "logit_gap": 1.0}}))
    (root / "metrics" / "throwaway_count.py").write_text(
        "def read(run):\n    return 42.0\n")
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "throwaway.cell",
                           "config": "internlm2-1.8b",
                           "traffic": "throwaway_mix", "chips": 1,
                           "why": "test"})
    m["per_layer"].append({"name": "throwaway_count", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving.scheduler", "moves": "tok_s",
                           "workloads": ["throwaway.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    parts = manifest.resolve("throwaway.cell", True, repo=tmp_path,
                             root=root)
    assert parts["traffic"]["deck"] == 2
    got = {m["name"]: mod for m, mod in parts["metrics"]}
    assert got["throwaway_count"].read(None) == 42.0
    # the new metric lists only the new cell
    old = manifest.resolve(MANIFEST["workloads"][0]["name"], True,
                           repo=tmp_path, root=root)
    assert "throwaway_count" not in [m["name"] for m, _ in old["metrics"]]


def test_a_missing_part_is_named(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    with pytest.raises(manifest.ManifestError, match="no-such-cell"):
        manifest.resolve("no-such-cell", False, repo=tmp_path)
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["workloads"][0]["traffic"] = "no_such_mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    with pytest.raises(manifest.ManifestError, match="no_such_mix"):
        manifest.resolve(m["workloads"][0]["name"], False, repo=tmp_path)
