"""Run one benchmark cell once and print its result.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run makes its tables from the seed,
warms up the cell's shapes (set-up), measures for ``--seconds``, checks
what the timed path produced against a float64 reference, and prints
one JSON object as the last line of standard output: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  The readings of the check,
each with its limit, are the last lines of standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is
kept in ``.jax_cache`` at the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # the cache lives at a fixed path inside the checkout; the program
    # reads the variable and sets no other
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # every program, however quick to compile, is cached after the
    # first run, so no later run compiles in its set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    cell = harness.Cell(args.workload, args.seed, args.seconds,
                        traced=bool(args.trace))
    line = harness.run(cell, T_START)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
