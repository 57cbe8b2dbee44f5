"""Readings for the correctness limit, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 --seconds <s> [--out <file.jsonl>]

For each seed it serves the cell as a run does (same set-up, lead-in and
window) and reads the gaps of the served greedy and sampled tokens against
the float32 reference (bench/lib/check.py): the sound readings. For the
control seeds it also reads the gaps of the tokens the int8 reference puts
in the program's place at the same positions: the control, which a limit
has to fail. Each limit in the cell's file (bench/cells/<cell>.json) is
set between the two (PERF.md gives the readings).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench.lib import chip, spec
    cell = spec.find_cell(args.workload)
    if not chip.open_chip(cell.chips):
        return 2
    for row in readings(cell, args.seeds, args.control_seeds, args.seconds):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


def readings(cell, seeds, control_seeds, seconds):
    """Yields one dict of readings per seed, with the run's end-to-end
    numbers beside them."""
    import jax
    from bench.lib import check, harness, result, weights
    hf = cell.config
    for seed in seeds:
        t0 = time.monotonic()
        w = weights.make(hf, hf["weights"], seed)
        jax.block_until_ready(w)
        served = harness.serve(cell, w, seed, seconds, t_start=t0)
        win = served.window
        row = {"workload": cell.name, "seed": seed,
               **check.readings(win.recs, win.w1, w, hf, seed,
                                control=seed in control_seeds),
               "wrong_length_requests":
                   check.wrong_length(win.recs, win.w1, hf["vocab_size"]),
               "end_to_end": result.end_to_end(win, served.setup_s),
               "kv_blocks_peak": win.kv_blocks_peak,
               "kv_blocks_usable": win.kv_blocks_usable,
               "memory_peak_bytes": served.memory_peak_bytes,
               "seconds": time.monotonic() - t0}
        yield row
        del w


if __name__ == "__main__":
    raise SystemExit(main())
