"""The control of the correctness comparison: the plain reference put in
the program's place, computed in the next precision below the one the
configuration states (every float32 column rounded to bfloat16, as a
change that stored columns in a narrower type would), compared with the
float32 reference by the same comparison a run uses.  It has to come out
as not correct; its smallest readings over the seeds are the upper
readings that the limits in the configuration file were set below.

    python chipbench/control.py --workload sf10-adhoc --seeds 11 12 13 \
        --queries 16

Runs on the host only (NumPy), at the cell's own size, over the first
``--queries`` queries of the cell's window schedule, the ones a run
answers.  It prints one JSON line per seed.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import check, datagen, harness, loadgen  # noqa: E402
from chipbench.reference import Reference  # noqa: E402


def window_queries(spec: dict, n: int) -> list:
    """The first ``n`` queries of the window's schedule, in the order
    the clients submit them, after the set-up's own steps."""
    streams = loadgen.Mix(spec).streams()
    warm = int(spec.get("warmup_steps", 0))
    out = []
    for step in range(warm + n):
        for stream in streams:
            group = next(stream)
            if step >= warm:
                out.extend(group)
        if len(out) >= n:
            break
    return out[:n]


def control_numbers(config: dict, spec: dict, seed: int, n: int) -> dict:
    """The compared numbers of the control on one seed."""
    catalog = datagen.generate(config, seed)
    ref, low = Reference(catalog), Reference(catalog, lower=True)
    queries = window_queries(spec, n)
    families = harness.load_families({f for f, _ in queries})
    got = {}
    for fam, params in queries:
        key = (fam, json.dumps(params, sort_keys=True))
        if key not in got:
            got[key] = families[fam].reference(low, params).result()
    answers = [(f, p, got[(f, json.dumps(p, sort_keys=True))])
               for f, p in queries]
    return check.check_answers(answers, ref, families)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--queries", type=int, required=True)
    args = parser.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)
    config = harness.load_json(harness.HERE / "configs"
                               / f"{cell['config']}.json")
    spec = loadgen.load(cell["traffic"])
    for seed in args.seeds:
        numbers = control_numbers(config, spec, seed, args.queries)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "queries": args.queries,
                          "correct": check.verdict(numbers, config["limits"]),
                          "checks": check.report(numbers, config["limits"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
