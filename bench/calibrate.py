"""Readings that the limits of ``correct`` are set from; not a benchmark run.

For one cell, on each seed given, one process runs the cell's window at
its own load for ``--seconds``, then reads the widest logit gap of the
program's served tokens and, on the first ``--control-seeds`` seeds, of
the tokens the float8 control puts first at the same positions of the
same sample (``bench/check.py``), with the harness's verdict on each.
One JSON line per seed, then a summary: the program's largest reading
(the lower end of a limit) and the control's smallest (the upper end).

    python3 bench/calibrate.py --workload nemo.chat --seconds 8 --control-seeds 3 \
        --seeds 101 102 103 104
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    try:
        devs = harness.chips(cell)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    rows = []
    for k, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0,
                               device_kind=devs[0].device_kind,
                               controls=k < args.control_seeds)
        row = {"seed": seed, "correct": out["correct"],
               "logit_gap": out["checks"]["logit_gap"]["value"],
               "cascade_mismatches": out["checks"]["cascade_mismatches"]["value"],
               "tokens_checked": out["tokens_checked"],
               "attempted": out["attempted"], "failed": out["failed"],
               "window_compiles": out["run"].window_compiles,
               "setup_s": out["run"].setup_s,
               "wall_s": time.perf_counter() - t0}
        if "control" in out:
            row["control_logit_gap"] = out["control"]["checks"]["logit_gap"]["value"]
            row["control_correct"] = out["control"]["correct"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    ctrl = [r["control_logit_gap"] for r in rows if "control_logit_gap" in r]
    summary = {"workload": args.workload,
               "program_max": max(r["logit_gap"] for r in rows),
               "control_min": min(ctrl) if ctrl else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
