"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload deepfm-h24.zipf --seed 7 --seconds 21 --trace 0

Runs on the machine it is started on and needs as many TPU chips as the
cell asks for: without them it exits non-zero and prints no result. The last
line of standard output is the result JSON; the compared numbers, each with
its limit, are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout root (for ``bench``) and the program's sources, never this
# script's own directory (its module names would shadow the standard library)
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(e, file=sys.stderr, flush=True)
        sys.exit(3)
    harness.emit(result)


if __name__ == "__main__":
    main()
