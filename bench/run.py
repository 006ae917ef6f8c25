#!/usr/bin/env python3
"""Serving benchmark: one run of one cell of BENCHMARK.json.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  Builds the
cell's model from the seed on the chip, warms the cell's own programs,
pre-rolls its traffic, measures for ``--seconds``, then checks a sample of
the served requests against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, last, ``checks``: each number compared
with its limit, as the last lines of standard error also give them.
Off a TPU, or with fewer chips than the cell needs, it prints no result
and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import sys                                               # noqa: E402
from pathlib import Path                                 # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
