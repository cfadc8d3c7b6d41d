#!/usr/bin/env python3
"""The control of `correct`: the plain reference put in the program's place
with one stated guarantee broken, which has to come out as not correct.

The configurations guarantee that no verdict is approximate or stale: the
verdict cache is exact by construction.  The control is what a later PR
would be tempted by: the reference behind a verdict cache of the program's
default capacity whose key is a 16-bit digest of the request, not the
request.  Two requests that share a digest share a verdict, and about half
of those shared verdicts are wrong.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s>

runs the cell as run.py does and then answers the same requests, in the
order they were sent, with the control.  It prints both readings: the
program's (the lower: 0 on every sound run) and the control's (the upper:
above 0, or the comparison cannot tell them apart).  The benchmark's own
runs never call this.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import zlib  # noqa: E402
from collections import OrderedDict  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import harness  # noqa: E402
import wire  # noqa: E402

CACHE_ENTRIES = 32768  # the program's default verdict cache
DIGEST_BITS = 16


def control_codes(sent_rows: np.ndarray, rows, expected: np.ndarray) -> np.ndarray:
    """What the control answers to the requests in the order they were sent."""
    mask = (1 << DIGEST_BITS) - 1
    digest = [zlib.crc32(wire.check_request(r)) & mask for r in rows]
    cache: "OrderedDict[int, int]" = OrderedDict()
    out = np.empty(len(sent_rows), dtype=np.int32)
    for k, row in enumerate(sent_rows.tolist()):
        key = digest[row]
        if key in cache:
            cache.move_to_end(key)
            out[k] = cache[key]
            continue
        out[k] = cache[key] = int(expected[row])
        if len(cache) > CACHE_ENTRIES:
            cache.popitem(last=False)
    return out


def readings(result, seconds: float):
    """(the program's compared numbers, the control's) of one finished run."""
    ev = result["evidence"]
    traffic, records = ev["traffic"], ev["records"]
    codes = control_codes(records["row"], traffic["rows"], traffic["expected"])
    ctl = harness.compare(records, traffic["expected"], seconds, codes)
    return ev["cmp"], ctl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    try:
        result = harness.run_cell(manifest, root, args.workload, args.seed,
                                  args.seconds, False, "tpu", T_START)
    except harness.Refused as e:
        print(f"benchmark: REFUSED: {e}", file=sys.stderr)
        return 1
    program, ctl = readings(result, args.seconds)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "program": dict(program["numbers"], correct=program["correct"],
                        compared=program["compared"]),
        "control": dict(ctl["numbers"], correct=ctl["correct"],
                        compared=ctl["compared"]),
        "metrics": result["metrics"], "device": result["device"]}))
    # the control has to fail, and the program to pass
    return 0 if program["correct"] and not ctl["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
