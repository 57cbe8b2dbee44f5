"""Serving CLI — a thin driver over the continuous-batching engine.

Closed-loop demo (trace mode): inject a Poisson arrival trace, serve it via
continuous batching over a paged (block-table) KV cache with chunked
prefill on a VirtualCluster whose autoscaling policy reads the engine's
published metrics, and watch the cluster grow 1->N while the queue is deep
and shrink back as it drains:

  PYTHONPATH=src python -m repro.launch.serve --trace poisson --smoke

One-shot baseline (the pre-continuous-batching path, kept for comparison and
for the token-for-token correctness tests):

  PYTHONPATH=src python -m repro.launch.serve --trace oneshot --smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.configs.base import ParallelPlan
from repro.core import ClusterImage, LatencyPolicy, QueueDepthPolicy, \
    VirtualCluster
from repro.core.clock import ManualClock
from repro.launch import steps as St
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as Mo
from repro.models.env import Env
from repro.serve import (SERVE_PLAN, SamplingParams, burst_trace,
                         make_scheduler_policy, make_serving_engine,
                         poisson_trace, repetitive_trace, run_to_completion,
                         sysprompt_trace)


def serve_batch(mesh, cfg, params, prompts, gen_len: int, plan,
                streamed_prefill: bool = False):
    """One-shot batch serving: prefill every prompt together, then decode
    the uniform batch to gen_len. The correctness baseline for the
    continuous-batching engine.

    streamed_prefill=True feeds the prompt token-by-token through the same
    decode step instead of one full-sequence prefill call — the one-shot
    baseline whose floating-point path matches *chunked* prefill (a full
    prefill reduces attention in GEMM order; per-token decode reduces
    per-query — same math, different fp association, so greedy argmax can
    flip on near-ties between the two; see docs/serving.md)."""
    env = Env(mesh=mesh, plan=plan)
    B, S = prompts.shape
    decode = jax.jit(St.make_decode_step(cfg, env), donate_argnums=(1,))

    if streamed_prefill:
        caches = Mo.init_cache(cfg, env, B, S + gen_len)
        logits = None
        for i in range(S):
            logits, caches = decode(params, caches, prompts[:, i:i + 1],
                                    jnp.asarray(i, jnp.int32))
    else:
        prefill = jax.jit(St.make_prefill_step(cfg, env))
        # allocate full-length caches, then write the prompt via prefill
        kw = {"tokens": prompts}
        if cfg.family == "vlm":
            kw["vision_embeds"] = jnp.zeros((B, cfg.num_vision_embeds,
                                             cfg.d_model), jnp.float32)
        if cfg.is_encdec:
            kw["frames"] = jnp.zeros((B, S // cfg.enc_downsample,
                                      cfg.d_model), jnp.float32)
        logits, caches = prefill(params, kw)
        # grow cache seq dim so decode can append (prefill emits length-S
        # caches; window rings stay at min(w, S + gen))
        caches = Mo.grow_caches(caches, gen_len, cfg)
    tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
    out = [tok]
    offset = cfg.num_vision_embeds if cfg.family == "vlm" else 0
    for i in range(gen_len - 1):
        logits, caches = decode(params, caches, tok,
                                jnp.asarray(S + offset + i, jnp.int32))
        tok = jnp.argmax(logits[:, :cfg.vocab_size], -1
                         ).astype(jnp.int32)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _build_policy(args):
    if args.policy == "latency":
        return LatencyPolicy(target_p95_ms=args.target_p95_ms,
                             min_nodes=args.nodes, max_nodes=args.max_nodes)
    return QueueDepthPolicy(target_per_node=args.queue_per_node,
                            min_nodes=args.nodes, max_nodes=args.max_nodes)


def _sampling_of(args) -> SamplingParams:
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.sample_seed)


def _trace_of(args, cfg):
    """Build the arrival trace (deterministic for the args — the sampled
    verify path regenerates it for a second engine)."""
    sampling = _sampling_of(args)
    if args.trace == "burst":
        return burst_trace(args.requests, prompt_len=args.prompt_len,
                           vocab_size=cfg.vocab_size, gen_len=args.gen,
                           deadline_s=args.deadline, sampling=sampling,
                           seed=args.seed)
    if args.trace == "sysprompt":
        return sysprompt_trace(args.requests, args.rate,
                               prompt_len=args.prompt_len,
                               vocab_size=cfg.vocab_size,
                               prefix_len=args.prefix_len, gen_len=args.gen,
                               gen_len_max=args.gen_max,
                               deadline_s=args.deadline, sampling=sampling,
                               seed=args.seed)
    if args.trace == "repetitive":
        return repetitive_trace(args.requests, args.rate,
                                prompt_len=args.prompt_len,
                                vocab_size=cfg.vocab_size, gen_len=args.gen,
                                gen_len_max=args.gen_max,
                                deadline_s=args.deadline, sampling=sampling,
                                seed=args.seed)
    return poisson_trace(args.requests, args.rate,
                         prompt_len=args.prompt_len,
                         vocab_size=cfg.vocab_size, gen_len=args.gen,
                         gen_len_max=args.gen_max, deadline_s=args.deadline,
                         sampling=sampling, seed=args.seed)


def _make_engine(args, cfg, params, *, num_slots=None, replicas=None,
                 clock=None, spec=None):
    """A ServingEngine (replicas == 1) or a Router + ReplicaSet data
    plane. --kv-blocks is per replica, so a fleet runs at replicas x that
    total budget — pass total/replicas to compare at equal KV bytes.
    `spec` overrides --spec (the --verify re-serve passes "off")."""
    sched = {"preemptive": True} if (args.sched == "edf"
                                     and args.edf_preempt) else {}
    return make_serving_engine(
        cfg, params,
        replicas=args.replicas if replicas is None else replicas,
        routing=args.routing, drain_mode=args.drain,
        num_slots=num_slots or args.slots,
        prompt_len=args.prompt_len, max_gen=args.gen_max,
        kv=args.kv, block_size=args.block_size,
        kv_blocks=args.kv_blocks,
        prefix_cache=args.prefix_cache == "on",
        prefill_chunk=args.prefill_chunk,
        spec=args.spec if spec is None else spec,
        spec_k=args.spec_k,
        swap=args.swap == "on",
        swap_budget_blocks=args.swap_budget_blocks,
        policy=make_scheduler_policy(args.sched, **sched),
        clock=clock)


def run_trace(args, cfg, params) -> int:
    policy = _build_policy(args)
    image = ClusterImage.build(f"{cfg.name}-serve", cfg, SERVE_PLAN, "serve")
    n0 = max(args.nodes, args.replicas)  # fleet replicas track nodes 1:1
    cluster = VirtualCluster(n_compute=n0, image=image, policy=policy,
                             cooldown_s=args.cooldown)
    print("serving replicas register to the catalog:\n" + cluster.hostfile)

    engine = _make_engine(args, cfg, params, clock=cluster.clock)
    multi = args.replicas > 1
    plane = engine.describe() if multi else engine.pool.describe()
    spec_tag = ("off" if args.spec == "off"
                else f"{args.spec} k={args.spec_k}")
    print(f"{plane}, chunked prefill="
          f"{engine.prefill_chunk or 'off'}, scheduler={engine.policy.name}, "
          f"spec={spec_tag}, "
          f"sampling={'greedy' if args.temperature <= 0 else _sampling_of(args)}")
    trace = _trace_of(args, cfg)

    sizes = []  # scaling timeline: (sim_t, n_compute)

    def on_step(i, snap, c):
        n = len(c.current_view().compute)
        if not sizes or sizes[-1][1] != n:
            sizes.append((c.clock.now(), n))
            extra = (f"  replicas={snap['replicas_live']:.0f}"
                     if multi else "")
            print(f"  t={c.clock.now():7.2f}s  nodes={n}  "
                  f"queue={snap['queue_depth']:.0f}  "
                  f"p95={snap.get('latency_p95_ms', 0.0):.0f}ms  "
                  f"occ={snap['slot_occupancy']:.2f}{extra}")

    if multi:
        # the fleet's speedup is real — every live replica decodes its own
        # batch within the tick — so one step costs step_time flat
        dt = args.step_time
    else:
        # one decode step costs step_time on one node; N data-parallel
        # serving replicas drain the shared queue ~N x faster (the PR-1
        # sim speedup model, kept for the single-engine baseline)
        dt = lambda n: args.step_time / max(n, 1)
    t0 = time.time()
    out = cluster.serve(engine, trace, dt=dt, on_step=on_step)
    wall = time.time() - t0

    peak = max((n for _, n in sizes), default=n0)
    final = len(cluster.current_view().compute)
    n_tok = sum(len(t) for t in out.values())
    snap = engine.snapshot()
    print(f"served {len(out)}/{len(trace)} requests, {n_tok} tokens "
          f"in {engine.clock.now():.2f}s sim ({wall:.2f}s wall)")
    print(f"autoscale: start={n0} peak={peak} final={final} "
          f"({len(cluster.scaler.history)} actions)")
    if multi:
        print(f"fleet: replicas live={snap['replicas_live']:.0f} "
              f"cold warmups={snap['replica_warmups']:.0f} "
              f"drained+released={len(engine.released)} "
              f"routing={engine.routing.name}")
    print(f"p50={snap.get('latency_p50_ms', 0.0):.0f}ms "
          f"p95={snap.get('latency_p95_ms', 0.0):.0f}ms "
          f"tokens/s(sim)={snap['tokens_per_s']:.1f}")
    if snap.get("prefix_hit_rate", 0.0) > 0.0:
        print(f"prefix cache: hit rate "
              f"{snap['prefix_hit_rate']:.2f}, prefill tokens computed "
              f"{snap['prefill_tokens']:.0f}, shared occupancy "
              f"{snap['kv_shared_occupancy']:.2f}")
    if "accepted_per_step" in snap:
        print(f"speculative ({spec_tag}): accepted/step "
              f"{snap['accepted_per_step']:.2f}, acceptance rate "
              f"{snap['spec_acceptance_rate']:.2f}")
    if args.swap == "on":
        print(f"swap tier: {snap.get('swapped_blocks', 0.0):.0f} blocks "
              f"out ({snap.get('swap_out_bytes', 0.0):.0f}B), "
              f"{snap.get('swap_in_bytes', 0.0):.0f}B restored, "
              f"recomputed tokens {snap.get('recomputed_tokens', 0.0):.0f}")
    if "kv_quant_divergence" in snap:
        print(f"quant KV: calibrated divergence "
              f"{snap['kv_quant_divergence']:.4f} relative RMS")

    rc = 0
    if args.verify:
        if multi:
            # the multi-replica acceptance bar: the same trace through a
            # single zero-router engine must emit bit-identical tokens —
            # routing, replica count, and any drain events along the way
            # are invisible in the output (greedy and seeded alike)
            eng2 = _make_engine(args, cfg, params, replicas=1,
                                clock=ManualClock())
            out2 = run_to_completion(eng2, _trace_of(args, cfg),
                                     dt=args.step_time)
            ok = out == out2
            print(f"verify {args.replicas} replicas "
                  f"({engine.routing.name} routing) vs 1: "
                  f"{'bit-identical MATCH' if ok else 'MISMATCH'}")
        elif args.spec != "off":
            # the speculative acceptance bar: the same trace served with
            # --spec off on a fresh engine must emit bit-identical tokens
            # — drafters and verify lanes are invisible in the output
            # (greedy and seeded alike; token-match acceptance is the
            # degenerate rejection-sampling residual, serve/spec.py)
            eng2 = _make_engine(args, cfg, params, spec="off",
                                clock=ManualClock())
            out2 = run_to_completion(eng2, _trace_of(args, cfg),
                                     dt=args.step_time)
            ok = out == out2
            print(f"verify --spec {args.spec} (k={args.spec_k}) vs "
                  f"--spec off: "
                  f"{'bit-identical MATCH' if ok else 'MISMATCH'}")
        elif args.kv == "quant" and args.temperature <= 0:
            # the quantized cache is bounded-divergence, not bit-exact, so
            # the fp one-shot is no oracle. Verify the invariance contract
            # it DOES keep: the same trace on a fresh quant engine with a
            # different slot count (different lane placements, batch
            # compositions — and swap/preemption events, if any) must emit
            # bit-identical tokens. tests/test_tiered_kv.py pins the
            # divergence bound against the fp engine separately.
            alt = args.slots // 2 if args.slots > 1 else args.slots + 1
            eng2 = _make_engine(args, cfg, params, num_slots=alt,
                                clock=ManualClock())
            out2 = run_to_completion(eng2, _trace_of(args, cfg),
                                     dt=args.step_time)
            ok = out == out2
            print(f"verify quant KV ({args.slots} vs {alt} slots): "
                  f"{'bit-identical MATCH' if ok else 'MISMATCH'}")
        elif args.temperature > 0:
            # seeded sampling has no one-shot oracle; verify the v2
            # contract instead: the same trace on a fresh engine with a
            # different slot count (different lane placements, different
            # batch compositions) must emit bit-identical tokens
            alt = args.slots // 2 if args.slots > 1 else args.slots + 1
            eng2 = _make_engine(args, cfg, params, num_slots=alt,
                                clock=ManualClock())
            out2 = run_to_completion(eng2, _trace_of(args, cfg),
                                     dt=args.step_time)
            ok = out == out2
            print(f"verify seeded sampling ({args.slots} vs {alt} slots): "
                  f"{'bit-identical MATCH' if ok else 'MISMATCH'}")
        else:
            prompts = jnp.asarray(np.stack([r.prompt for r in trace]))
            # chunked prefill's fp path matches the streamed-prefill
            # one-shot (full-prefill GEMM reassociates; docs/serving.md)
            streamed = bool(engine.prefill_chunk)
            base = np.asarray(serve_batch(None, cfg, params, prompts,
                                          args.gen_max, SERVE_PLAN,
                                          streamed_prefill=streamed))
            # slice by the *admitted* budget (gen_len capped by
            # max_tokens) — submit() no longer rewrites r.gen_len
            ok = all(np.array_equal(base[r.rid][:r.eff_gen_len],
                                    np.array(out[r.rid]))
                     for r in trace)
            tag = "streamed-prefill one-shot" if streamed else "one-shot"
            print(f"verify vs {tag} baseline: "
                  f"{'token-for-token MATCH' if ok else 'MISMATCH'}")
        rc = 0 if ok else 1
    cluster.shutdown()
    return rc


def run_oneshot(args, cfg, params) -> int:
    image = ClusterImage.build(f"{cfg.name}-serve", cfg, SERVE_PLAN, "serve")
    cluster = VirtualCluster(n_compute=args.nodes, image=image)
    print("serving replicas register to the catalog:\n" + cluster.hostfile)
    rng = jax.random.PRNGKey(args.seed)
    prompts = jax.random.randint(rng, (args.requests, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    t0 = time.time()
    toks = cluster.submit(serve_batch, cfg, params, prompts, args.gen,
                          SERVE_PLAN)
    dt = time.time() - t0
    n_tok = args.requests * args.gen
    print(f"generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s on this CPU sim)")
    print("sample:", np.asarray(toks[0])[:16])
    cluster.shutdown()
    return 0


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="paper-demo")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "burst", "sysprompt", "repetitive",
                             "oneshot"))
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--gen-max", type=int, default=None,
                    help="max gen length (default: --gen)")
    ap.add_argument("--rate", type=float, default=16.0,
                    help="poisson arrival rate, requests/s (sim time)")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots per replica (max concurrent "
                    "decodes each)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas, each with its own KV pool and "
                    "prefix cache; a Router admits requests across them "
                    "and the autoscaler drains/spawns them live (1 = the "
                    "zero-router single-engine data plane)")
    ap.add_argument("--routing", default="occupancy",
                    choices=("occupancy", "prefix"),
                    help="replica routing policy: least committed KV, or "
                    "prefix-affine (route to the replica whose prefix "
                    "cache holds the prompt's longest prefix)")
    ap.add_argument("--drain", default="finish",
                    choices=("finish", "preempt"),
                    help="scale-down drain mode: let a draining replica's "
                    "requests finish, or restart-preempt them back to the "
                    "router queue (bit-identical either way)")
    ap.add_argument("--kv", default="paged",
                    choices=("paged", "quant", "slot"),
                    help="paged block-table cache, int8-quantized paged "
                    "cache (~2x blocks per byte, bounded divergence), or "
                    "PR-1 slot reservation")
    ap.add_argument("--swap", default="off", choices=("on", "off"),
                    help="host swap tier for paged/quant KV: preemptions "
                    "copy victim blocks to host RAM and resume "
                    "bit-identically with zero recompute")
    ap.add_argument("--swap-budget-blocks", type=int, default=None,
                    help="host swap residency cap in blocks (default: "
                    "unbounded); a full budget falls back to restart "
                    "preemption")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV: tokens per block")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV: physical blocks (default: worst case)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill lane width (0 disables; default: "
                    "prompt_len on attention-only archs)")
    ap.add_argument("--prefix-cache", default="on", choices=("on", "off"),
                    help="paged KV: share full prompt-prefix blocks across "
                    "requests (copy-on-write; exact, greedy and seeded)")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="sysprompt trace: shared system-prompt length "
                    "(default: 3/4 of --prompt-len)")
    ap.add_argument("--spec", default="off",
                    choices=("off", "ngram", "model"),
                    help="speculative decoding drafter: prompt-lookup "
                    "self-drafting (ngram) or a tiny draft model; the "
                    "target verifies k drafts per slot in one fused step "
                    "and output stays bit-identical to --spec off")
    ap.add_argument("--spec-k", default="4",
                    help="draft tokens proposed per slot per step, or "
                    "'auto': adapt each request's draft depth from its own "
                    "acceptance feedback (AIMD, floor 1)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k logit cutoff (0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="sampling PRNG root (per-request seeds derive "
                    "from it; output is reproducible and lane-invariant)")
    ap.add_argument("--sched", default="fifo", choices=("fifo", "edf"),
                    help="admission-order scheduler policy")
    ap.add_argument("--edf-preempt", action="store_true",
                    help="EDF only: allow restart-preemption of the "
                    "slackest running request for an urgent arrival")
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="per-request completion deadline, seconds (EDF "
                    "orders by it; misses feed the autoscaler)")
    ap.add_argument("--nodes", type=int, default=1,
                    help="initial / minimum compute nodes")
    ap.add_argument("--max-nodes", type=int, default=6)
    ap.add_argument("--policy", default="queue", choices=("queue", "latency"))
    ap.add_argument("--queue-per-node", type=int, default=2)
    ap.add_argument("--target-p95-ms", type=float, default=400.0)
    ap.add_argument("--step-time", type=float, default=0.05,
                    help="simulated seconds per decode step on one node")
    ap.add_argument("--cooldown", type=float, default=0.3,
                    help="autoscaler cooldown between actions (sim seconds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="check tokens against the one-shot baseline")
    args = ap.parse_args()
    if args.spec_k != "auto":
        args.spec_k = int(args.spec_k)
    if args.gen_max is None:
        args.gen_max = args.gen
    if args.prefix_len is None:
        args.prefix_len = (3 * args.prompt_len) // 4
    if (args.trace == "sysprompt" and args.prefix_cache == "on"
            and args.prefix_len < args.block_size):
        print(f"warning: --prefix-len {args.prefix_len} < --block-size "
              f"{args.block_size}: the shared prefix spans no full block, "
              "so the prefix cache cannot hit (try --block-size 4)")

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    rng = jax.random.PRNGKey(0)
    params = Mo.init_params(rng, cfg, Env(mesh=None, plan=SERVE_PLAN))

    if args.trace == "oneshot":
        return run_oneshot(args, cfg, params)
    return run_trace(args, cfg, params)


if __name__ == "__main__":
    raise SystemExit(main())
