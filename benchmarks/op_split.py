"""Device time of chosen programs in one cell of the chip benchmark
(``chipbench``), split by XLA operation.

Runs the cell as ``chipbench/run.py --trace 1`` does.  Before the
harness reduces the profiler's trace, it sums the device time of every
operation that runs inside the named programs (``--programs``, as the
result's ``breakdown`` names them), three ways: by the operation's name
with its numeric suffix dropped (``scatter.3`` -> ``scatter``), by the
trace's HLO category, and by the JAX operation the HLO came from (its
``tf_op``, the last path element).  The last line of standard output is
one JSON object: the cell's ``correct``, ``metrics``, ``device`` and
``breakdown``, and the split (seconds).  ``--out`` also writes it, with
the full trace statistics of the three longest events of each name.

Run:  python benchmarks/op_split.py --workload sf10-adhoc --seed 7 \\
          --programs jit__join_expand,jit__compact_nz_impl
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def split_ops(path: str, programs) -> dict:
    """{program: {"by_name"|"by_category"|"by_op": {key: seconds}}} over
    the device planes of one ``.xplane.pb``, plus ``examples``."""
    from jax.profiler import ProfileData

    from chipbench import tracereduce

    split = {p: {"total": 0.0, "by_name": defaultdict(float),
                 "by_category": defaultdict(float),
                 "by_op": defaultdict(float)} for p in programs}
    examples = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((float(e.start_ns), float(e.duration_ns),
                       tracereduce._module_name(e.name))
                      for e in lines.get(tracereduce.MODULE_LINE, []))
        starts = [m[0] for m in mods]
        for e in lines.get(tracereduce.OP_LINE, []):
            ts = float(e.start_ns)
            i = bisect.bisect_right(starts, ts) - 1
            if i < 0 or ts > mods[i][0] + mods[i][1]:
                continue
            prog = mods[i][2]
            if prog not in split:
                continue
            s = float(e.duration_ns) * 1e-9
            stats = {k: v for k, v in e.stats}
            short = e.name.partition(" = ")[0].lstrip("%")
            name = re.sub(r"(\.\d+)+$", "", short)
            op = str(stats.get("tf_op", "")).rsplit("/", 1)[-1]
            out = split[prog]
            out["total"] += s
            out["by_name"][name] += s
            out["by_category"][str(stats.get("hlo_category", ""))] += s
            out["by_op"][op] += s
            examples[(prog, name)].append(
                (s, e.name[:400], {k: str(v)[:400] for k, v in
                                   stats.items()}))
    for prog, out in split.items():
        for k in ("by_name", "by_category", "by_op"):
            out[k] = dict(sorted(out[k].items(), key=lambda kv: -kv[1]))
    ex = {f"{p} {n}": sorted(v, key=lambda t: -t[0])[:3]
          for (p, n), v in examples.items()}
    return {"split": split, "examples": ex}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--programs", required=True,
                        help="comma-separated program names")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    programs = [p for p in args.programs.split(",") if p]

    from chipbench import harness, tracereduce

    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)
    devices = harness.require_chips(int(cell["chips"]))
    found = {}
    extract = tracereduce.extract

    def extract_and_split(path, *a, **kw):
        found.update(split_ops(path, programs))
        return extract(path, *a, **kw)

    tracereduce.extract = extract_and_split
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, t_process=T_PROCESS,
                                  devices=devices, bench=bench)
    finally:
        tracereduce.extract = extract
    line = {"workload": args.workload, "seed": args.seed,
            **{k: result.get(k) for k in ("correct", "attempted", "failed",
                                          "metrics", "device",
                                          "breakdown")},
            "split": found.get("split")}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(dict(line, examples=found.get("examples")), f,
                      indent=1)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
