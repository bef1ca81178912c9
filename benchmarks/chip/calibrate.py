"""Several runs of one cell in one process, for setting limits and bounds.

    python3 benchmarks/chip/calibrate.py --workload internlm2_1_8b.decode_backlog \
        --seeds 11 12 13 --seconds 30 [--override '{"packed_values": "int4"}']

Each seed is a whole run (weights, engine, warm-up, window, check), as
``run.py`` makes it; compiled programs carry over between seeds.  With
``--override`` the serve settings of the configuration are replaced: the
program's int4 path is the correctness control of an int8 cell.  Prints one
JSON line per seed.  It needs the chip, like ``run.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--override", default="{}", help="JSON of serve settings to replace")
    args = ap.parse_args()

    import harness

    for seed in args.seeds:
        t0 = time.monotonic()
        r = harness.run(args.workload, seed, args.seconds, False, t_start=t0,
                        overrides=json.loads(args.override))
        print(json.dumps({"seed": seed, "override": json.loads(args.override),
                          "wall_s": time.monotonic() - t0, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
