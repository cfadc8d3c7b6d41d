#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout.  Prints the contract's line as the last
line of standard output, or, where the run proves nothing (no TPU, a device
failure absorbed, a compilation inside the window, no system under test),
the reasons on standard error and a non-zero exit.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # setup_s counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        # the accepted platform is not an argument: this command measures a TPU
        result = harness.run_cell(manifest, root, args.workload, args.seed,
                                  args.seconds, bool(args.trace), "tpu", T_START)
    except harness.Refused as e:
        print(f"benchmark: REFUSED: {e}", file=sys.stderr)
        return 1
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
