"""The executor's device-to-host reads and re-dispatches in one cell of
the chip benchmark (``chipbench``).

Runs the cell as ``chipbench/run.py --trace 0`` does and prints, as the
last line, one JSON object: the growth of the program's ``exec.*``
registry counters across the measured window (``exec.host_syncs``,
``exec.deferred_dispatches`` and ``exec.redispatches``, the last two
also per operator as ``{op=...}``, and ``exec.joins`` by
``{path=unique|expand}``), the queries answered in it, the run's
``correct`` verdict and its end-to-end ``metrics``.

Run:  python benchmarks/exec_counters.py --workload sf1-dashboard --seed 7
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
    parser.add_argument("--seconds", type=float, default=51.0)
    args = parser.parse_args(argv)

    from chipbench import harness

    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)
    devices = harness.require_chips(int(cell["chips"]))
    kept = {}

    def at_window_start(svc):
        kept["registry"] = reg = svc.session.telemetry().registry
        kept["before"] = reg.snapshot()["counters"]

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              False, t_process=T_PROCESS, devices=devices,
                              bench=bench, window_hook=at_window_start)
    before = kept["before"]
    grown = {k: v - before.get(k, 0) for k, v in
             sorted(kept["registry"].snapshot()["counters"].items())
             if k.startswith("exec.")}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": result["correct"],
                      "answered": result["attempted"] - result["failed"],
                      "counters": grown, "metrics": result["metrics"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
