#!/usr/bin/env python3
"""Readings behind a cell's correctness limit, all seeds in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--control]

For each seed it makes a benchmark run of the cell (set-up and window as
``run.py`` does, the window at the cell's own load), compares what was
served with the reference and judges it by the cell's limits as
``run.py`` does, printing one JSON line per seed with the program's
readings and ``correct``.  With ``--control`` it also reads the float8
control on the same prompts and tokens (``reference.max_gap``), puts it in
the program's place (``reference.as_control``) and judges it the same way
(``control_correct``, which has to come out false).  The lower reading of
a limit is the largest program reading over a dozen seeds or more, the
upper one the smallest control reading (see PERF.md).
Needs the chip(s) the cell asks for; the benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import reference, run  # noqa: E402


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.serve_cell(types.SimpleNamespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=0), Path(root))
        res = reference.max_gap(out.c, seed, out.checked,
                                control=args.control)
        line = {"seed": seed, **res, "failed": out.failed,
                "correct": run.judge(out.limits, res, out.failed)[0]}
        if args.control:
            line["control_correct"] = run.judge(
                out.limits, reference.as_control(res), out.failed)[0]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
