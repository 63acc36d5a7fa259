"""The readings that set the limits of ``correct``, for one cell on the chip.

    python3 perfbench/control.py --workload douban-r20.uniform \
        --seeds 11,12,13 --seconds 10

Runs the cell once per seed in one process (set-up is paid once per
graph, compiles once), each with a short window at the cell's own load,
and compares the same seeded sample of the window's answers twice: as the
program served them (the lower reading) and as the control answers them
(the upper reading).  The control is the plain reference in the
program's place with one guarantee broken: one shortest path per answer
instead of every shortest path.  One JSON line per seed, then a summary.
The benchmark's own runs (``run.py``) never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    cell = harness.load_cell(root, args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run(root, cell, seed, args.seconds, False,
                              t_start=time.perf_counter(), control=True,
                              log=lambda m: print(m, file=sys.stderr))
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": out["correct"],
               "checked": out["load"]["checked"],
               "program": {k: c["value"] for k, c in out["checks"].items()},
               "control_mismatched": out["control"]["mismatched_answers"],
               "metrics": {k: m["value"] for k, m in out["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_mismatched": max(r["program"]["mismatched_answers"] for r in rows),
        "upper_mismatched": min(r["control_mismatched"] for r in rows),
        "all_correct": all(r["correct"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
