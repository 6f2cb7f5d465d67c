#!/usr/bin/env python3
"""Record the small trace that tests/chipbench/test_chipbench_trace.py
reads: one traced run of a cell on the chip, reduced by
`tracereduce.load_xplane` as every run reduces it, clipped to its first
quarter second.

  python3 chipbench/testdata/record_trace.py --workload <cell> --seed <n>
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CLIP_S = 0.25


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=str(HERE / "trace_small.json"))
    a = ap.parse_args(argv)
    sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]
    from chipbench import run, tracereduce

    load = tracereduce.load_xplane
    got = []

    def keep(path):
        got.append(load(path))
        return got[-1]
    tracereduce.load_xplane = keep
    rc = run.run(["--workload", a.workload, "--seed", str(a.seed),
                  "--seconds", str(a.seconds), "--trace", "1"])
    tr = got[0]
    t0 = tr.span("cb.traced")[0][1]
    Path(a.out).write_text(tr.clipped(t0, t0 + CLIP_S).to_json())
    return rc


if __name__ == "__main__":
    sys.exit(main())
