"""Readings of a cell's program and of its control, for setting limits.

    python benchmark/readings.py --workload <cell> --program 1 2 ... --control 1 2 3

One process, one line of JSON per seed.  ``--program`` runs the cell's
timed path once per seed, as a run does (set-up, then a window of one
pipeline, then the check), on a TPU; its readings give each limit's
lower end.  ``--control`` puts the cell's float64 reference, computed
one precision below each precision the configuration states
(``reference/precision.py``), in the program's place: the same tables
from the same seed, the same sampled series, the same comparison.  It
runs no program; its readings give the upper ends.  The limits and the
readings they were set from are in ``limits/<cell>.json`` and PERF.md.
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def program(name: str, seed: int) -> dict:
    from spans import Spans

    cell = harness.Cell(name, seed, 0.0)
    driver = harness.plugin("drivers", cell.traffic["driver"])
    spans = Spans()
    state = driver.setup(cell, spans, _log)
    records = driver.window(state, 0.0, spans, _log)
    answers = driver.release(state)
    return {**driver.check(cell, answers, _log), "pipelines": len(records)}


def control(name: str, seed: int) -> dict:
    cell = harness.Cell(name, seed, 0.0)
    driver = harness.plugin("drivers", cell.traffic["driver"])
    gen = harness.plugin("data", cell.config["generator"])
    tables = gen.make(cell.config, cell.rng("data"))
    t = cell.config["tables"]
    return driver.control(cell, {"left": tables[t["left"]["name"]],
                                 "right": tables[t["right"]["name"]]})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--program", type=int, nargs="*", default=[])
    parser.add_argument("--control", type=int, nargs="*", default=[])
    args = parser.parse_args(argv)
    if args.program:
        import device

        cell = harness.Cell(args.workload, 0, 0.0)
        device.require(int(cell.workload["chips"]))
    for kind, seeds in (("program", args.program), ("control", args.control)):
        read = program if kind == "program" else control
        for seed in seeds:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              kind: read(args.workload, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
