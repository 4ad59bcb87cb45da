#!/usr/bin/env python3
"""The control of the `correct` comparison: the plain reference put in
the program's place, judged by the same comparison and limits as a run.
It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 \
        [--rank bfloat16] [--report float64]

The program ranks each mapspace's candidates by EDP in float32 and
reports its winners' cycles, energy and network totals in float64.  The
control ranks every mapspace of the query in `--rank` and reports in
`--report`: bfloat16 ranking with float64 reports (one step below the
ranking, the program's answer otherwise) or float32 for both (one step
below the reports).  For each seed it prints the numbers compared, each
beside its limit, as one JSON line; it exits 0 when the control is
judged not correct on every seed, and 1 otherwise.  It runs on the host
alone, at the cell's own size."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.cell import resolve                                  # noqa: E402
from bench.reference.check import Reference, judge, load_limits  # noqa: E402


def control_numbers(config, traffic, seed: int, rank: str,
                    report: str = "float64"):
    ref = Reference(config, traffic, seed)
    return ref.compare(ref.control_answers(rank, report))


def limits():
    """The run's limits, but for `query_mismatch`: the control answers
    once."""
    return {k: v for k, v in load_limits().items()
            if k != "query_mismatch"}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rank", default="bfloat16")
    ap.add_argument("--report", default="float64")
    args = ap.parse_args(argv)
    cell = resolve(args.workload, root)
    lim = limits()
    all_failed = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        nums = control_numbers(cell.config, cell.traffic, seed, args.rank,
                               args.report)
        ok, lines = judge(nums, lim)
        all_failed &= not ok
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "rank": args.rank, "report": args.report,
                          "correct": ok,
                          "seconds": time.perf_counter() - t0,
                          "compared": {k: {"value": nums[k], "limit": v}
                                       for k, v in lim.items()}}),
              flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
