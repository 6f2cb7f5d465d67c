#!/usr/bin/env python3
"""Readings that set a cell's correctness limit (not part of a run).

  python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, in one process: a run of the cell as run.py makes it (a
short window at the cell's own load), then the reference over a sample
of the served requests, and on the same prompts and tokens the fp8
control (reference.py). One JSON line per seed: the program's reading
(`logit_gap`, the lower end of the limit's range over a dozen seeds) and
the control's (`control_gap`, the upper end).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    import jax

    from chipbench import manifest, run as R
    parts = manifest.resolve(a.workload, False)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        R.fail(f"JAX finds no TPU (platform {devs[0].platform!r})")
    peak = json.loads(R.PEAKS.read_text())[devs[0].device_kind]
    for seed in (int(s) for s in a.seeds.split(",")):
        args = R.parse_args(["--workload", a.workload, "--seed", str(seed),
                             "--seconds", str(a.seconds)])
        out = R.run_cell(args, parts, devs, peak, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "logit_gap": out["check"]["logit_gap"]["value"],
                          "control_correct": out["control"]["correct"],
                          "control_gap":
                              out["control"]["check"]["logit_gap"]["value"],
                          "tokens": out["check"]["tokens_compared"]["value"],
                          "limit": out["check"]["logit_gap"]["limit"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
