"""Rollout CLI — the serving fleet as a post-training generation engine.

Closed-loop demo: fan a prompt set out as seeded rollouts over the
continuous-batching fleet (the autoscaler grows into the burst), score
the completions, build chosen/rejected pairs, and step the serving
model's own params with a DPO update — then sample the next round from
the freshly trained policy:

  PYTHONPATH=src python -m repro.launch.rollout --smoke --verify

Multi-turn trace (completions re-enter the queue as follow-ups with grown
shared prefixes — the prefix-cache + affine-routing stress test):

  PYTHONPATH=src python -m repro.launch.rollout --trace multiturn --smoke

--verify checks the reproducibility contract that makes rollouts usable
as training data: the same prompt set through --replicas N and through a
single engine with a different slot count must emit bit-identical
completions per (prompt, sample, turn) coordinate.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core import ClusterImage, LatencyPolicy, QueueDepthPolicy, \
    VirtualCluster
from repro.core.clock import ManualClock
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as Mo
from repro.models.env import Env
from repro.optim.adamw import AdamWConfig
from repro.rollout import (PreferenceTrainer, RolloutEngine, RolloutLoop,
                           make_scorer, rollout_signature)
from repro.serve import (SERVE_PLAN, SamplingParams, make_scheduler_policy,
                         make_serving_engine)


def _build_policy(args):
    if args.policy == "latency":
        return LatencyPolicy(target_p95_ms=args.target_p95_ms,
                             min_nodes=args.nodes, max_nodes=args.max_nodes)
    return QueueDepthPolicy(target_per_node=args.queue_per_node,
                            min_nodes=args.nodes, max_nodes=args.max_nodes)


def _sampling_of(args) -> SamplingParams:
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.sample_seed)


def _prompts_of(args, cfg):
    rng = np.random.default_rng(args.seed)
    return [rng.integers(0, cfg.vocab_size, size=(args.prompt_len,),
                         dtype=np.int32) for _ in range(args.prompts)]


def _make_engine(args, cfg, params, *, replicas=None, num_slots=None,
                 clock=None):
    """Engine budgeted for the multi-turn context growth: turn t prompts
    are base + t*gen tokens, so prompt_len covers the final turn."""
    return make_serving_engine(
        cfg, params,
        replicas=args.replicas if replicas is None else replicas,
        routing=args.routing,
        num_slots=num_slots or args.slots,
        prompt_len=args.prompt_len + (args.turns - 1) * args.gen,
        max_gen=args.gen,
        kv=args.kv, block_size=args.block_size,
        prefix_cache=True,
        prefill_chunk=args.prefill_chunk,
        policy=make_scheduler_policy("fifo"),
        clock=clock)


def _make_scorer(args, cfg, params):
    if args.scorer == "length":
        return make_scorer("length", target=args.gen)
    if args.scorer == "logprob":
        return make_scorer("logprob", cfg=cfg, params=params)
    # keyword: reward the low-id eighth of the vocab — an arbitrary but
    # deterministic target the DPO rounds can visibly steer toward
    return make_scorer("keyword",
                       keywords=tuple(range(max(cfg.vocab_size // 8, 1))))


def run(args, cfg, params) -> int:
    sampling = _sampling_of(args)
    prompts = _prompts_of(args, cfg)
    n_req = args.prompts * args.n_samples

    rc = 0
    if args.verify:
        # the acceptance bar for rollouts-as-training-data: completions
        # are a pure function of (params, prompt, derived seed) — fleet
        # size, slot count, and lane placement must not show in a token
        eng_a = _make_engine(args, cfg, params, clock=ManualClock())
        ro_a = RolloutEngine(eng_a, n_samples=args.n_samples,
                             gen_len=args.gen, sampling=sampling)
        sig_a = rollout_signature(ro_a.generate(prompts, dt=args.step_time,
                                                turns=args.turns))
        alt = args.slots // 2 if args.slots > 1 else args.slots + 1
        eng_b = _make_engine(args, cfg, params, replicas=1, num_slots=alt,
                             clock=ManualClock())
        ro_b = RolloutEngine(eng_b, n_samples=args.n_samples,
                             gen_len=args.gen, sampling=sampling)
        sig_b = rollout_signature(ro_b.generate(prompts, dt=args.step_time,
                                                turns=args.turns))
        ok = sig_a == sig_b
        print(f"verify rollouts: {args.replicas} replicas x {args.slots} "
              f"slots vs 1 replica x {alt} slots: "
              f"{'bit-identical MATCH' if ok else 'MISMATCH'} "
              f"({len(sig_a)} rollouts)")
        rc |= 0 if ok else 1

    image = ClusterImage.build(f"{cfg.name}-rollout", cfg, SERVE_PLAN,
                               "serve")
    n0 = max(args.nodes, args.replicas)
    cluster = VirtualCluster(n_compute=n0, image=image,
                             policy=_build_policy(args),
                             cooldown_s=args.cooldown)
    print("rollout replicas register to the catalog:\n" + cluster.hostfile)

    engine = _make_engine(args, cfg, params, clock=cluster.clock)
    multi = args.replicas > 1
    plane = engine.describe() if multi else engine.pool.describe()
    print(f"{plane}, sampling={sampling}, scorer={args.scorer}, "
          f"n_samples={args.n_samples}, turns={args.turns}")

    sizes = []  # capacity timeline across serve/train phases

    def on_step(i, snap, c):
        n = len(c.current_view().compute)
        if not sizes or sizes[-1][1] != n:
            sizes.append((c.clock.now(), n))

    ro = RolloutEngine(engine, n_samples=args.n_samples, gen_len=args.gen,
                       sampling=sampling)
    trainer = PreferenceTrainer(
        cfg, params, beta=args.beta,
        opt=AdamWConfig(lr=args.lr, warmup_steps=0,
                        total_steps=max(args.rounds * args.train_steps, 1),
                        weight_decay=0.0))
    loop = RolloutLoop(cluster, ro, _make_scorer(args, cfg, params), trainer,
                       prompts=prompts, dt=args.step_time, turns=args.turns,
                       train_steps=args.train_steps, on_step=on_step)

    t0 = time.time()
    for rnd in range(args.rounds):
        m = loop.round()
        nodes = len(cluster.current_view().compute)
        print(f"  round {rnd}: {m['rollout_tokens']:.0f} rollout tokens, "
              f"reward_mean={m['reward_mean']:.4f}, "
              f"pairs={m['pairs_per_round']:.0f}, "
              f"train_loss={m['train_loss']:.4f}  (nodes={nodes})")
    wall = time.time() - t0

    peak = max((n for _, n in sizes), default=n0)
    final = len(cluster.current_view().compute)
    snap = engine.snapshot()
    print(f"{args.rounds} rounds x {n_req} rollouts in "
          f"{cluster.clock.now():.2f}s sim ({wall:.2f}s wall); "
          f"autoscale start={n0} peak={peak} final={final} "
          f"({len(cluster.scaler.history)} actions)")
    if snap.get("prefix_hit_rate", 0.0) > 0.0:
        print(f"prefix cache: hit rate {snap['prefix_hit_rate']:.2f} "
              f"(multi-turn lineages and {args.n_samples}-way sibling "
              f"fan-out share prompt blocks)")

    # the loop's phase metrics arbitrate capacity through the same
    # registry the serve snapshots use — show what the policy last saw
    ms = cluster.scaler.read_metrics(cluster.registry)
    got = {k: ms.get(k) for k in ("rollout_tokens", "reward_mean",
                                  "pairs_per_round", "train_loss")}
    print(f"autoscaler view: {got}")
    rc |= 0 if all(v is not None for v in got.values()) else 1

    if args.verify:
        h0, hN = loop.history[0], loop.history[-1]
        dec = h0["train_loss"] < h0["train_loss_first"] or \
            hN["train_loss"] < h0["train_loss_first"]
        print(f"verify training: loss {h0['train_loss_first']:.4f} -> "
              f"{hN['train_loss']:.4f} over {args.rounds} rounds: "
              f"{'DECREASING' if dec else 'NOT DECREASING'}")
        rc |= 0 if dec else 1
        improved = hN["reward_mean"] >= h0["reward_mean"]
        print(f"reward_mean {h0['reward_mean']:.4f} -> "
              f"{hN['reward_mean']:.4f} "
              f"({'improved/held' if improved else 'regressed'})")

    loop.retire()
    cluster.shutdown()
    return rc


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="paper-demo")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trace", default="burst",
                    choices=("burst", "multiturn"),
                    help="burst: every prompt's samples arrive at once; "
                    "multiturn: completions re-enter the queue as "
                    "follow-up turns with grown shared prefixes")
    ap.add_argument("--prompts", type=int, default=4,
                    help="distinct prompts per round")
    ap.add_argument("--n-samples", type=int, default=4,
                    help="sampled completions per prompt (the rollout "
                    "fan-out; seeds derive per (prompt, sample))")
    ap.add_argument("--turns", type=int, default=1,
                    help="conversation turns per lineage (multiturn "
                    "trace forces >= 2)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="generate -> score -> train rounds")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=8,
                    help="completion length per turn")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--routing", default="prefix",
                    choices=("occupancy", "prefix"),
                    help="prefix-affine routing keeps a lineage's turns "
                    "on the replica caching its grown prefix")
    ap.add_argument("--kv", default="paged", choices=("paged", "quant"))
    ap.add_argument("--block-size", type=int, default=4,
                    help="small blocks so short shared prefixes span "
                    "full blocks (prefix-cache hits)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill lane width (required for "
                    "variable-length multi-turn prompts; default: auto)")
    ap.add_argument("--scorer", default="keyword",
                    choices=("keyword", "length", "logprob"))
    ap.add_argument("--temperature", type=float, default=0.7,
                    help="> 0 so a prompt's samples differ (greedy "
                    "rollouts all tie and yield no preference pairs)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--sample-seed", type=int, default=0)
    ap.add_argument("--beta", type=float, default=0.5,
                    help="DPO inverse-temperature")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--train-steps", type=int, default=4,
                    help="optimizer steps per round")
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--max-nodes", type=int, default=6)
    ap.add_argument("--policy", default="queue", choices=("queue", "latency"))
    ap.add_argument("--queue-per-node", type=int, default=2)
    ap.add_argument("--target-p95-ms", type=float, default=400.0)
    ap.add_argument("--step-time", type=float, default=0.05)
    ap.add_argument("--cooldown", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="check bit-reproducibility across fleet shapes "
                    "and that the DPO loss decreases")
    args = ap.parse_args()
    if args.trace == "multiturn":
        args.turns = max(args.turns, 2)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    rng = jax.random.PRNGKey(0)
    params = Mo.init_params(rng, cfg, Env(mesh=None, plan=SERVE_PLAN))
    return run(args, cfg, params)


if __name__ == "__main__":
    raise SystemExit(main())
