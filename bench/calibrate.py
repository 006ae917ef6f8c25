#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        --seconds <s> --control-bits 8 --out <file.jsonl>

Runs the cell once per seed in this one process (the programs compile
once) and, for each, reads the widest gap of the program's served tokens
and of the control: the plain reference with every linear's input rounded
to ``--control-bits`` signed integers, put in the program's place at the
same prompts and positions.  One JSON line per seed goes to ``--out``.
The benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control-bits", type=int, nargs="+", default=[8])
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    import harness
    with open(args.out, "a") as f:
        for seed in args.seeds:
            t = time.perf_counter()
            r = harness.run(args.workload, seed, args.seconds, False,
                            harness.Options(controls=tuple(args.control_bits)),
                            t_start=t)
            c = r["checks"]
            line = {"workload": args.workload, "seed": seed,
                    "control_bits": args.control_bits,
                    "tokens": c.get("tokens_compared", {}).get("value"),
                    "program": c.get("program_stats", {}).get("value"),
                    "control": c.get("control_stats", {}).get("value"),
                    "seconds": time.perf_counter() - t}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
