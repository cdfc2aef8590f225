"""The one traffic generator: reads ``chipbench/traffic/<name>.json``.

A traffic file describes a closed loop: how many clients, what each
submits at once (one query, or every panel of a dashboard), and the
parameter pools of each query family.  The queries are the same for
every seed: the seed makes the data (``datagen``), and every seed serves
the same set of sizes.  Within a run no ad-hoc literal repeats until its
family's pool is used up.

Keys of a traffic file:

* ``clients``: number of concurrent clients.
* ``kind``: ``"adhoc"``: client *i* cycles through ``cycle``, starting
  at position *i* mod its length, one query at a time, each with its
  family's next literals; ``"dashboard"``: client *i* is a dashboard of
  one panel per family of ``cycle``, with its own literals, and submits
  all its panels at once.
* ``cycle``: family names (``chipbench/families/<name>.py``).
* ``pools``: family -> list of groups; a group maps each parameter to a
  list of values, and the family's pool is the union of the groups'
  cartesian products.
* ``order_seed``: the fixed shuffle of each pool, so that consecutive
  queries of a family do not sweep its range in order.
* ``warmup_steps``: steps each client runs before the window to build
  the state the traffic needs (a dashboard's panels are the covering
  expressions its refreshes reuse); the window's schedule continues
  from there.
* ``replay_steps``: for a periodic schedule, the steps of the window's
  schedule that set-up runs first, on a service of its own, so that the
  window compiles nothing.  Without it set-up replays the schedule for
  the window's seconds, plus the seconds spent compiling, stopping on
  whole MQO windows as the window does.
"""
from __future__ import annotations

import itertools
import json
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
Query = Tuple[str, dict]


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def pool(groups: List[Dict[str, list]]) -> List[dict]:
    out = []
    for g in groups:
        keys = list(g)
        for vals in itertools.product(*(g[k] for k in keys)):
            out.append(dict(zip(keys, vals)))
    return out


class Mix:
    """Query streams of one traffic file."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.pools = {f: pool(spec["pools"][f]) for f in spec["cycle"]}
        for f, entries in self.pools.items():
            if not entries:
                raise ValueError(f"empty parameter pool for {f}")

    def _draws(self) -> Dict[str, Iterator[dict]]:
        """Per family: the pool in its fixed shuffled order, again and
        again (each lap another shuffle)."""
        out = {}
        for k, f in enumerate(self.spec["cycle"]):
            rng = np.random.default_rng([self.spec["order_seed"], k])
            entries = self.pools[f]

            def gen(rng=rng, entries=entries):
                while True:
                    for j in rng.permutation(len(entries)):
                        yield entries[j]
            out[f] = gen()
        return out

    def streams(self) -> List[Iterator[List[Query]]]:
        """One iterator per client; each step yields the queries the
        client submits at once."""
        spec = self.spec
        cycle, n = spec["cycle"], spec["clients"]
        draws = self._draws()
        if spec["kind"] == "dashboard":
            boards = [[(f, next(draws[f])) for f in cycle] for _ in range(n)]
            return [itertools.repeat(b) for b in boards]
        if spec["kind"] != "adhoc":
            raise ValueError(f"unknown traffic kind {spec['kind']!r}")
        # one schedule, filled step by step in client order, so that a
        # client's k-th query does not depend on when the others asked
        sched: List[List[List[Query]]] = [[] for _ in range(n)]

        def client(i):
            for k in itertools.count():
                while len(sched[i]) <= k:
                    step = len(sched[0])
                    for j in range(n):
                        f = cycle[(j + step) % len(cycle)]
                        sched[j].append([(f, next(draws[f]))])
                yield sched[i][k]
                sched[i][k] = None      # served: free it
        return [client(i) for i in range(n)]

    def queries_per_step(self) -> int:
        return (len(self.spec["cycle"]) if self.spec["kind"] == "dashboard"
                else 1)
