"""Run one cell of the chip benchmark; the last line of standard output
is the result as one JSON object.

    python chipbench/run.py --workload sf1-dashboard --seed 7 \
        --seconds 51 --trace 0

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The compared numbers and their limits are the
last lines of standard error and the ``checks`` key of the result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from chipbench import harness

    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)
    try:
        devices = harness.require_chips(int(cell["chips"]))
    except harness.NoChip as exc:
        print(f"chipbench: {exc}; nothing was run", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              devices=devices, bench=bench)
    for name, v in result["checks"].items():
        print(f"check {name} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
