"""Run one cell of the chip benchmark and print its result.

    python3 benchmarks/chip/run.py --workload internlm2_1_8b.decode_backlog \
        --seed 7 --seconds 30 --trace 0

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; see ``harness.py``.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
Everything else goes to standard error, which ends with those numbers too.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for.  It never falls back to the CPU.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the trace and a description of its planes into DIR")
    args = ap.parse_args()

    import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START, keep_trace=args.keep_trace)
    except harness.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
