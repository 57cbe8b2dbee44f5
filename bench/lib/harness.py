"""One run of a serving cell, timed from the client side.

Set-up makes the weights from the seed, builds the engine through the
program's public constructor, warms the step shapes the cell's traffic
uses, and runs a lead-in of the cell's own traffic. The window then drives
ServingEngine.step() open-loop for a fixed number of seconds: every request
was submitted with its due time on the engine's clock, and every token is
stamped when step() returns it to the host. The program's counters are
read as differences over the window.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import traffic, weights, work
from bench.lib import trace as T
from repro.configs.base import ModelConfig
from repro.core.clock import RealClock
from repro.models import model as Mo
from repro.models.env import Env, vocab_pad
from repro.serve import (SERVE_PLAN, FIFOPolicy, Request, SamplingParams,
                         make_serving_engine)

# seconds of the window the device trace covers, in its middle
TRACE_SLICE_S = 6.0
# output tokens and prompt of the warm-up requests: one prompt longer
# than a lane's budget opens two lane steps in a row, and two decode
# steps follow, so the (rows, previous rows) shape pairs are all run
WARM_GEN = 3


def model_config(hf: dict) -> ModelConfig:
    """The program's config for a configuration file's published keys."""
    return ModelConfig(
        name=hf["name"], family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], head_dim=hf["head_dim"],
        qkv_bias=hf["attention_bias"], rope_theta=float(hf["rope_theta"]),
        norm_eps=hf["rms_norm_eps"], tie_embeddings=hf["tie_word_embeddings"],
        block_pattern=("attn",), source=hf["source"])


def program_params(mcfg: ModelConfig, hf: dict, w: dict) -> dict:
    """The canonical weights in the program's layout, checked leaf by leaf
    against the shapes and types the program's own init would make."""
    env = Env(None, SERVE_PLAN)
    params = weights.program_params(w, hf, vocab_pad(mcfg, env))
    want = jax.eval_shape(lambda k: Mo.init_params(k, mcfg, env),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if got != want:
        raise RuntimeError(f"{mcfg.name}: the benchmark's weights do not "
                           f"match the program's parameter layout:\n{got}\n"
                           f"vs\n{want}")
    return params


def build_engine(mcfg: ModelConfig, params, e: dict):
    if e["policy"] != "fifo" or e["kv"] != "paged":
        raise ValueError(f"engine settings not served here: {e}")
    return make_serving_engine(
        mcfg, params, replicas=1, clock=RealClock(), policy=FIFOPolicy(),
        num_slots=e["num_slots"], prompt_len=e["prompt_len"],
        max_gen=e["max_gen"], kv="paged", block_size=e["block_size"],
        prefix_cache=e["prefix_cache"], prefill_chunk=e["prefill_chunk"],
        spec=None, swap=False)


def _sampling(s: Optional[traffic.Sampling]) -> SamplingParams:
    if s is None:
        return SamplingParams()
    return SamplingParams(temperature=s.temperature, top_p=s.top_p,
                          top_k=s.top_k, seed=s.seed)


def warm_up(eng, mix: dict, e: dict, vocab: int, seed: int) -> None:
    """Run every step shape the mix uses once: for each sampling kind in
    the mix (greedy, sampled), one request whose prompt spans two lane
    steps, then two decode steps."""
    rng = np.random.default_rng(seed)
    share = mix["sampling"]["greedy_share"]
    kinds = ([None] if share > 0 else []) + (
        [traffic.Sampling(0.7, 0.9, 50, 1)] if share < 1 else [])
    for i, kind in enumerate(kinds):
        prompt = rng.integers(0, vocab, size=(e["prefill_chunk"] + 16,),
                              dtype=np.int32)
        eng.submit([Request(rid=-1 - i, prompt=prompt, gen_len=WARM_GEN,
                            arrival_t=eng.clock.now(),
                            sampling=_sampling(kind))])
        while not eng.drained():
            eng.step()


@dataclass
class Rec:
    """A request as the client sees it."""
    spec: traffic.RequestSpec
    req: Request
    due: float
    token_times: List[float] = field(default_factory=list)
    cached: Optional[int] = None  # prompt positions the prefix cache served
    t_done: Optional[float] = None

    @property
    def t_admit(self) -> Optional[float]:
        return self.req.t_admit

    @property
    def plen(self) -> int:
        return len(self.spec.prompt)


@dataclass
class SliceWork:
    """Work of the real rows run in the traced slice (bench/lib/work.py)."""
    flops: float = 0.0
    pd_flops: float = 0.0
    pd_bytes: float = 0.0
    mask_bytes: float = 0.0
    rows: int = 0
    steps: int = 0
    consistent: bool = True  # the lane model matched every step


@dataclass
class Window:
    w0: float
    w1: float
    recs: List[Rec]
    step_s: List[float]  # window step() calls that ran a fused step
    counters: Dict[str, float]  # program counters' window differences
    work: SliceWork
    trace: Optional[T.Trace]
    kv_blocks_peak: int  # most pool blocks live requests held in a step
    kv_blocks_usable: int


def _counters(eng) -> Dict[str, float]:
    return {"prefix_hit_tokens": eng.pool.prefix_hit_tokens,
            "prefix_lookup_tokens": eng.pool.prefix_lookup_tokens,
            "prefill_tokens": eng.metrics.prefill_tokens,
            "decode_steps": eng.decode_steps}


def drive(eng, recs: List[Rec], dims: work.Dims, *, w0: float, w1: float,
          trace_dir: Optional[str] = None) -> Window:
    """Serve the schedule open-loop until w1 on the engine's clock."""
    clock = eng.clock
    pending = deque(recs)
    live: List[Rec] = []
    lanes: List[list] = []  # [rec, next prompt position], admission order
    step_s: List[float] = []
    sw = SliceWork()
    c0 = None
    pf_last = eng.metrics.prefill_tokens
    ds_last = eng.decode_steps
    kv_peak = 0
    t_slice = (w0 + max(w1 - w0 - TRACE_SLICE_S, 0.0) / 2
               if trace_dir else None)
    tracing = traced = False
    span = lambda name: (jax.profiler.TraceAnnotation(name) if tracing
                         else nullcontext())
    while True:
        now = clock.now()
        if trace_dir and not traced:
            if not tracing and now >= t_slice:
                jax.profiler.start_trace(trace_dir)
                tracing = True
            elif tracing and now >= t_slice + TRACE_SLICE_S:
                jax.profiler.stop_trace()
                tracing, traced = False, True
        if now >= w1:
            break
        if c0 is None and now >= w0:
            c0 = _counters(eng)
        while pending and pending[0].due <= now:
            live.append(pending.popleft())
        if not live:
            with span("idle wait for arrival"):
                time.sleep(max(min(pending[0].due if pending else w1, w1)
                               - now, 0.0))
            continue
        with span("admit+step"):
            ts = clock.now()
            eng.step()
            t = clock.now()
        with span("harness bookkeeping"):
            # the engine may have admitted what fell due during the step
            while pending and pending[0].due <= t:
                live.append(pending.popleft())
            ran = eng.decode_steps != ds_last
            ds_last = eng.decode_steps
            if ran and ts >= w0:
                step_s.append(t - ts)
            if t >= w0:
                kv_peak = max(kv_peak, eng.pool.blocks_in_use)
            pf = eng.metrics.prefill_tokens
            _account(eng, live, lanes, pf - pf_last, t, dims,
                     sw if tracing else None)
            pf_last = pf
            live = [r for r in live if r.t_done is None]
    if tracing:
        jax.profiler.stop_trace()
    c1 = _counters(eng)
    c0 = c0 or c1
    tr = T.load_profile(trace_dir) if trace_dir and (traced or tracing) \
        else None
    return Window(w0, w1, recs, step_s,
                  {k: c1[k] - c0[k] for k in c1}, sw, tr, kv_peak,
                  eng.pool.usable_blocks)


def _account(eng, live: List[Rec], lanes: List[list], prefilled: int,
             t: float, dims: work.Dims, sw: Optional[SliceWork]) -> None:
    """After one step(): stamp the tokens it returned, and follow the
    prefill lanes. The lanes' rows are the step's prefill counter spread
    over the open lanes in admission order, as the engine packs its lane
    budget; a lane that runs out must be the one whose first token this
    step returned, which is checked."""
    for r in live:
        if r.cached is None and r.t_admit is not None:
            r.cached = eng.pool.cached_prefix_len(eng.replica.slot_of(r.req))
            lanes.append([r, r.cached])
    seq_keys: Dict[int, int] = {}
    keys: List[int] = []
    closed = set()
    for ln in lanes:
        if prefilled <= 0:
            break
        r, pos = ln
        take = min(prefilled, r.plen - pos)
        if sw is not None:
            keys.extend(range(pos + 1, pos + take + 1))
            seq_keys[r.spec.rid] = pos + take
        ln[1] = pos + take
        prefilled -= take
        if ln[1] == r.plen:
            closed.add(r.spec.rid)
    ok = prefilled == 0
    lanes[:] = [ln for ln in lanes if ln[1] < ln[0].plen]
    first = set()
    sampled = 0
    for r in live:
        n = len(r.req.tokens)
        seen = len(r.token_times)
        if n > seen:
            if seen == 0:
                first.add(r.spec.rid)
            r.token_times.extend([t] * (n - seen))
            if sw is not None:
                for i in range(max(seen, 1), n):
                    keys.append(r.plen + i)
                    seq_keys[r.spec.rid] = max(seq_keys.get(r.spec.rid, 0),
                                               r.plen + i)
                if r.spec.sampling is not None:
                    sampled += n - seen
        if r.req.done:
            r.t_done = t
    ok = ok and first == closed
    if sw is not None:
        sw.consistent &= ok
        sw.steps += 1
        sw.rows += len(keys)
        sw.flops += sum(work.row_flops(dims, k) for k in keys)
        sw.pd_flops += sum(work.attn_flops(dims, k) for k in keys)
        sw.pd_bytes += work.paged_attn_bytes(dims, seq_keys.values(),
                                             len(keys))
        sw.mask_bytes += work.mask_bytes(dims, sampled)


def schedule(cell, seed: int, seconds: float, t0: float) -> List[Rec]:
    """The cell's requests for one run, due from t0 on the engine clock."""
    mix = cell.mix
    horizon = float(mix["lead_in_s"]) + seconds
    specs = traffic.generate(mix, seed=seed, rate_rps=cell.settings["rate_rps"],
                             horizon_s=horizon,
                             vocab_size=cell.config["vocab_size"])
    return [Rec(s, Request(rid=s.rid, prompt=s.prompt, gen_len=s.gen_len,
                           arrival_t=t0 + s.offset_s,
                           sampling=_sampling(s.sampling)), t0 + s.offset_s)
            for s in specs]


def memory_peak_bytes() -> int:
    stats_ = jax.devices()[0].memory_stats() or {}
    return int(stats_.get("peak_bytes_in_use", 0))


@dataclass
class Served:
    window: Window
    setup_s: float
    memory_peak_bytes: int


def serve(cell, w: dict, seed: int, seconds: float, *, t_start: float,
          trace_dir: Optional[str] = None) -> Served:
    """Set-up, lead-in and window of one run over the canonical weights
    w. The engine is gone when this returns: its KV pool is freed before
    the reference runs."""
    hf, e = cell.config, cell.config["engine"]
    mcfg = model_config(hf)
    params = program_params(mcfg, hf, w)
    eng = build_engine(mcfg, params, e)
    warm_up(eng, cell.mix, e, hf["vocab_size"], seed)
    t0 = eng.clock.now()
    recs = schedule(cell, seed, seconds, t0)
    eng.submit([r.req for r in recs])
    w0 = t0 + float(cell.mix["lead_in_s"])
    win = drive(eng, recs, work.Dims.from_config(hf), w0=w0,
                w1=w0 + seconds, trace_dir=trace_dir)
    jax.block_until_ready(eng.pool.caches)
    peak = memory_peak_bytes()
    del eng, params
    gc.collect()
    return Served(win, w0 - t_start, peak)
