"""Run one cell of the serving benchmark on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
BENCHMARK.json at the checkout's root. --trace 0 prints the cell's
end-to-end metrics, --trace 1 its per-layer metrics from a profiled slice
of the window. The last line of standard output is one JSON object:
correct, attempted, failed, metrics, device (and breakdown when traced),
then check, the numbers compared with their limits, which also close
standard error. Exits non-zero with no result where JAX finds no TPU or
fewer chips than the cell asks for. JAX's compilation cache is kept in
<checkout>/.jax_cache, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from bench.lib import spec
    cell = spec.find_cell(args.workload)

    from bench.lib import chip
    if not chip.open_chip(cell.chips):
        return 2

    from bench.lib import result
    out = result.run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START)
    for name, v in out["check"].items():
        print(f"check {name}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
