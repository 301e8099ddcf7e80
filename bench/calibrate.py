"""Readings that set a cell's limit: ``harness.run`` on each seed in one
process, with one engine, so that set-up compiles once.

    python3 bench/calibrate.py --workload <name> --seconds 30 --seeds 11 12 13
    python3 bench/calibrate.py --workload <name> --seconds 30 --seeds 11 --control

Each seed serves the cell's traffic for ``--seconds``, as a run does, and
compares as many tokens.  With ``--control`` the float8 control takes the
program's place in the comparison.  One JSON line per seed on standard
output.  Like ``run.py`` it measures nothing off a TPU.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    cell = harness.load_cell(args.workload, ROOT)
    eng = harness.build_engine(cell)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.run(cell, seed, args.seconds, False, t0=t0, root=ROOT,
                        control=args.control, engine=eng)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": r["correct"], "checks": r["checks"],
                          "metrics": r["metrics"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
