"""The knee sweep of one cell: the cell's traffic at several offered
rates, one window each, in one process.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds <s> \
        --seed <n> [--out <file.jsonl>]

For each rate it prints, when the window opens and when it closes, the
admission backlog (requests due but not admitted), the requests awaiting
their first token and the requests in the system (due but not finished);
then completions, the most KV blocks live requests held, output tokens
per second and the TTFT and gap tails. The knee is the highest rate at
which the requests in the system do not pile up across the window and the
output tokens per second keep up with those offered (the rate times the
mix's mean output length); the cell's file then fixes its rate as a
number (PERF.md records the sweep).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def backlog(recs, t: float) -> int:
    """Requests due by t and not admitted by t."""
    return sum(1 for r in recs if r.due <= t
               and (r.t_admit is None or r.t_admit > t))


def awaiting_first(recs, t: float) -> int:
    """Requests due by t whose first token had not reached the host by t:
    the admission backlog and the prefill queue together."""
    return sum(1 for r in recs if r.due <= t
               and (not r.token_times or r.token_times[0] > t))


def in_system(recs, t: float) -> int:
    """Requests due by t and not finished by t."""
    return sum(1 for r in recs if r.due <= t
               and (r.t_done is None or r.t_done > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench.lib import chip, spec
    cell = spec.find_cell(args.workload)
    if not chip.open_chip(cell.chips):
        return 2
    rates = [float(x) for x in args.rates.split(",")]
    for row in sweep(cell, rates, args.seconds, args.seed):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


def sweep(cell, rates, seconds: float, seed: int):
    """Yields one dict per offered rate."""
    import jax
    from bench.lib import harness, result, stats, weights
    hf = cell.config
    w = weights.make(cell.reference.shapes(hf), hf["weights"], seed)
    jax.block_until_ready(w)
    for rate in rates:
        c = dataclasses.replace(cell, settings={**cell.settings,
                                                 "rate_rps": rate})
        served = harness.serve(c, w, seed, seconds,
                               t_start=time.monotonic())
        win = served.window
        e2e = result.end_to_end(win, served.setup_s)
        ttft = stats.ttft_samples(win.recs, win.w0, win.w1)
        gaps = stats.gap_samples(win.recs, win.w0, win.w1)
        row = {"workload": cell.name, "rate_rps": rate,
               "backlog_open": backlog(win.recs, win.w0),
               "backlog_close": backlog(win.recs, win.w1),
               "awaiting_first_open": awaiting_first(win.recs, win.w0),
               "awaiting_first_close": awaiting_first(win.recs, win.w1),
               "in_system_open": in_system(win.recs, win.w0),
               "in_system_close": in_system(win.recs, win.w1),
               "kv_blocks_peak": win.kv_blocks_peak,
               "kv_blocks_usable": win.kv_blocks_usable,
               "arrived": sum(1 for r in win.recs
                              if win.w0 <= r.due < win.w1),
               "completed": sum(1 for r in win.recs if r.t_done is not None
                                and win.w0 <= r.t_done <= win.w1),
               "ttft_p50_ms": (stats.quantile(ttft, .5) or 0) * 1e3,
               "itl_ms_q50_q90_q99": [(stats.quantile(gaps, q) or 0) * 1e3
                                      for q in (.5, .9, .99)],
               "gaps": len(gaps),
               **{k: v for k, v in e2e.items() if k != "setup_s"},
               "step_ms_mean": 1e3 * sum(win.step_s) / max(len(win.step_s),
                                                           1)}
        yield row


if __name__ == "__main__":
    raise SystemExit(main())
