"""One run of a cell from the seed to the contract's result line."""
from __future__ import annotations

import shutil
import tempfile
from dataclasses import dataclass
from typing import Optional

import jax

from bench.lib import check, harness, spec, stats, weights, work
from bench.lib import trace as T


@dataclass
class Ctx:
    """What a per-layer metric's reader reads."""
    window: harness.Window
    summary: Optional[T.Summary]  # the device trace; None off the TPU
    peak: Optional[dict]  # the chip's peaks; None off the TPU
    dims: work.Dims
    config: dict


def device_info(peak_bytes: int) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "memory_peak_bytes": peak_bytes}


def end_to_end(win: harness.Window, setup_s: float) -> dict:
    """Every end-to-end number a serving cell can report (None: no
    sample)."""
    secs = win.w1 - win.w0
    ttft = stats.quantile(stats.ttft_samples(win.recs, win.w0, win.w1), .95)
    itl = stats.quantile(stats.gap_samples(win.recs, win.w0, win.w1), .95)
    return {"ttft_p95_ms": None if ttft is None else ttft * 1e3,
            "itl_p95_ms": None if itl is None else itl * 1e3,
            "output_tokens_per_s":
                stats.tokens_in(win.recs, win.w0, win.w1) / secs,
            "setup_s": setup_s}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    hf = cell.config
    w = weights.make(hf, hf["weights"], seed)
    jax.block_until_ready(w)
    on_tpu = jax.devices()[0].platform == "tpu"
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        served = harness.serve(cell, w, seed, seconds, t_start=t_start,
                               trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    win = served.window
    numbers = check.judge(win.recs, win.w1, w, hf, seed,
                          cell.settings["limits"])
    dev = device_info(served.memory_peak_bytes)
    out = {"correct": check.holds(numbers),
           "attempted": sum(1 for r in win.recs if win.w0 <= r.due < win.w1),
           "failed": numbers["wrong_length_requests"]["value"],
           "metrics": {}, "device": dev}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        e2e = end_to_end(win, served.setup_s)
        for m in cell.end_to_end:
            if e2e[m["name"]] is not None:
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": units[m["name"]]}
    else:
        summary = T.reduce(win.trace) if on_tpu and win.trace else None
        ctx = Ctx(win, summary, spec.peaks(dev["kind"]) if on_tpu else None,
                  work.Dims.from_config(hf), hf)
        for m in cell.per_layer:
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v,
                                             "unit": units[m["name"]]}
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
            out["breakdown"] = {
                "device_ops": [list(x) for x in summary.top_ops()],
                "idle_gaps": [list(x) for x in summary.idle_gaps[:10]]}
    out["check"] = numbers
    return out
