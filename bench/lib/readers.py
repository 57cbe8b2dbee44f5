"""The per-layer quantities behind the readers in bench/metrics/. Each
returns None where the run has nothing to read: no sample, no device trace,
no chip peaks (off the TPU), or a slice whose lane accounting did not
match the program's counters."""
from __future__ import annotations

from typing import Optional

from bench.lib import stats, work

# op-name fragments of the kernels in a TPU trace: a Pallas call appears
# as a custom-call named after the jitted op that wraps it
PAGED_DECODE = "%paged_flash_decode"
SAMPLING_MASK = "%topk_topp_mask"


def queue_wait_p95_ms(ctx) -> Optional[float]:
    w = ctx.window
    q = stats.quantile(stats.queue_wait_samples(w.recs, w.w0, w.w1), .95)
    return None if q is None else q * 1e3


def prefix_hit_rate(ctx) -> Optional[float]:
    c = ctx.window.counters
    if c["prefix_lookup_tokens"] <= 0:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prefix_lookup_tokens"]


def kv_pool_peak_share(ctx) -> Optional[float]:
    """Most blocks that live requests held at once in the window, as a
    share of the pool the engine reserved."""
    w = ctx.window
    if w.kv_blocks_usable <= 0 or w.kv_blocks_peak <= 0:
        return None
    return 100.0 * w.kv_blocks_peak / w.kv_blocks_usable


def step_ms(ctx) -> Optional[float]:
    s = ctx.window.step_s
    return 1e3 * sum(s) / len(s) if s else None


def _device(ctx):
    sw = ctx.window.work
    if ctx.summary is None or ctx.peak is None or not sw.consistent \
            or sw.steps == 0:
        return None
    return sw


def step_mfu(ctx) -> Optional[float]:
    sw = _device(ctx)
    if sw is None:
        return None
    return 100.0 * sw.flops / ctx.summary.window_s \
        / ctx.peak["bf16_flops_per_s"]


def _roofline(ctx, kernel: str, flops: float, nbytes: float):
    t = ctx.summary.kernel_s(kernel)
    if t <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    return 100.0 * work.least_seconds(flops, nbytes, ctx.peak) / t


def paged_decode_roofline(ctx) -> Optional[float]:
    sw = _device(ctx)
    return None if sw is None else _roofline(ctx, PAGED_DECODE, sw.pd_flops,
                                             sw.pd_bytes)


def sampling_mask_roofline(ctx) -> Optional[float]:
    sw = _device(ctx)
    return None if sw is None else _roofline(ctx, SAMPLING_MASK, 0.0,
                                             sw.mask_bytes)


def device_idle_share(ctx) -> Optional[float]:
    if ctx.summary is None or ctx.peak is None:
        return None
    return 100.0 * ctx.summary.idle_share
