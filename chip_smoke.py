"""Chip smoke: drive the serving path and the elastic cluster on a TPU.

    python chip_smoke.py              # one chip: kernels, then qwen2-1.5b serving
    python chip_smoke.py --four-chip  # four chips: elastic 2 -> 4 node training

One process, phases in order; each phase is a function of its config, so
the tests run the same code at smoke size on the CPU.

  device   platform, device_kind and count; anything but a TPU exits
           non-zero before any work (no CPU fallback).
  kernels  paged_flash_decode (bf16 and int8), topk_topp_mask and
           flash_attention at qwen2-1.5b widths, each compiled, checked for
           a Mosaic kernel (tpu_custom_call) and compared with its ref.py
           oracle within the tolerances below.
  serve    qwen2-1.5b at published widths, random weights from --seed,
           served through make_serving_engine: paged KV (block 16), prefix
           cache, chunked prefill, 8 sysprompt requests (prompt 512, shared
           prefix 384, gen 32, 8 slots), once greedy and once seeded. Every
           request must finish with its token count and in-vocab ids, and
           the fused steps that ran must hold the Pallas paged-decode (and,
           seeded, sampling) kernels. Agreement with attn_impl="naive" is
           printed for information only (random weights: near-ties flip).
  elastic  (--four-chip only) a VirtualCluster of 2 nodes trains
           paper-demo-110m (seq 512, batch 8) through ElasticTrainer,
           scale_to(4) reshards it onto a real 4-device mesh, and each
           step's loss must match the same steps on one chip.

The last line of standard output is {"ok": true, "device": {...}}, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time
from dataclasses import dataclass, replace

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config, get_smoke  # noqa: E402
from repro.configs.base import ParallelPlan, ShapeConfig  # noqa: E402
from repro.core import VirtualCluster  # noqa: E402
from repro.core.clock import ManualClock  # noqa: E402
from repro.core.elastic import ElasticTrainer  # noqa: E402
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.kernels.paged_decode.ops import (  # noqa: E402
    paged_flash_decode, paged_flash_decode_quant, quantize_kv)
from repro.kernels.paged_decode.ref import (  # noqa: E402
    paged_decode_quant_ref, paged_decode_ref)
from repro.kernels.sampling.ops import topk_topp_mask  # noqa: E402
from repro.kernels.sampling.ref import (  # noqa: E402
    NEG_INF, topk_topp_mask_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as Mo  # noqa: E402
from repro.models.env import Env  # noqa: E402
from repro.serve import (SERVE_PLAN, SamplingParams,  # noqa: E402
                         make_serving_engine, run_to_completion,
                         sysprompt_trace)

# max |kernel - oracle| over the output; K/V and q are bf16, so the
# kernels' f32 math differs from the f32 oracle by bf16-level rounding
ATTN_TOL = 3e-2
# the mask kernel's keep-set may differ from the sort-based oracle only at
# a row's top-p boundary, where Mosaic's and XLA's exp and sums round
# differently: at most one entry per row
MASK_TOL_ENTRIES = 1
# relative loss difference per step, sharded mesh vs one chip
LOSS_RTOL = 2e-2


def _require(ok, msg) -> None:
    """A phase check (kept under python -O, unlike assert)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def _model(arch: str, smoke: bool):
    return get_smoke(arch) if smoke else get_config(arch)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelConfig:
    arch: str = "qwen2-1.5b"
    smoke: bool = False
    rows: int = 8  # decode rows
    block_size: int = 16
    max_blocks: int = 34  # (prompt 512 + gen 32) / block 16
    prefill_len: int = 512
    interpret: bool = False  # Pallas interpreter (CPU tests only)
    seed: int = 0


def _run_compiled(fn, *args):
    """Compile fn for args and run that executable: (out, compiled HLO)."""
    compiled = jax.jit(fn).lower(*args).compile()
    return jax.block_until_ready(compiled(*args)), compiled.as_text()


def _report(name: str, metric: str, err: float, tol: float, hlo: str,
            c: KernelConfig):
    mosaic = "tpu_custom_call" in hlo
    print(f"kernel {name}: {metric}={err!r} tol={tol!r} "
          f"tpu_custom_call={mosaic}", flush=True)
    _require(err <= tol, f"{name}: {metric} {err} above {tol}")
    _require(c.interpret or mosaic, f"{name}: no Mosaic kernel in the HLO")


def _paged_inputs(key, cfg, c: KernelConfig):
    B, MB, bs = c.rows, c.max_blocks, c.block_size
    nb = 1 + B * MB  # block 0 is the null block, as in serve/blocks.py
    kq, kk, kv, kl = jax.random.split(key, 4)
    pool = (nb, cfg.n_kv_heads, bs, cfg.head_dim)
    q = jax.random.normal(kq, (B, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kp = jax.random.normal(kk, pool, jnp.bfloat16)
    vp = jax.random.normal(kv, pool, jnp.bfloat16)
    perm = np.random.default_rng(c.seed).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(B, MB), jnp.int32)
    # random depths, plus a fully masked row and a full-table row
    lengths = jax.random.randint(kl, (B,), 0, MB * bs)
    lengths = lengths.at[0].set(-1).at[-1].set(MB * bs - 1)
    return q, kp, vp, tables, lengths


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def kernel_phase(c: KernelConfig) -> None:
    cfg = _model(c.arch, c.smoke)
    keys = jax.random.split(jax.random.PRNGKey(c.seed), 3)
    q, kp, vp, tables, lengths = _paged_inputs(keys[0], cfg, c)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_ref(q, kp, vp, tables, lengths)
    got, hlo = _run_compiled(
        lambda *a: paged_flash_decode(*a, interpret=c.interpret),
        q, kp, vp, tables, lengths)
    _report(f"paged_flash_decode bf16 {cfg.name}", "max_abs_err",
            _max_err(got, want), ATTN_TOL, hlo, c)

    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_quant_ref(q, kq, vq, ks, vs, tables, lengths)
    got, hlo = _run_compiled(
        lambda *a: paged_flash_decode_quant(*a, interpret=c.interpret),
        q, kq, vq, ks, vs, tables, lengths)
    _report(f"paged_flash_decode int8 {cfg.name}", "max_abs_err",
            _max_err(got, want), ATTN_TOL, hlo, c)

    S, Hq, Hkv, hd = c.prefill_len, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq_, kk_, kv_ = jax.random.split(keys[1], 3)
    qa = jax.random.normal(kq_, (1, S, Hq, hd), jnp.bfloat16)
    ka = jax.random.normal(kk_, (1, S, Hkv, hd), jnp.bfloat16)
    va = jax.random.normal(kv_, (1, S, Hkv, hd), jnp.bfloat16)
    # an f32 q keeps the oracle's output unrounded: the kernel's bf16
    # output is then off by at most half a bf16 ulp
    with jax.default_matmul_precision("highest"):
        want = attention_ref(*(x.transpose(0, 2, 1, 3).astype(jnp.float32)
                               for x in (qa, ka, va)), causal=True)
    want = want.transpose(0, 2, 1, 3).reshape(1, S, Hq * hd)
    got, hlo = _run_compiled(
        lambda *a: flash_attention(*a, causal=True, n_kv_heads=Hkv,
                                   interpret=c.interpret), qa, ka, va)
    _report(f"flash_attention prefill S={S} {cfg.name}", "max_abs_err",
            _max_err(got, want), ATTN_TOL, hlo, c)

    V = cfg.vocab_size
    logits = jax.random.normal(keys[2], (c.rows, V), jnp.float32) * 4.0
    top_k = jnp.asarray(np.resize([0, 40, 1, 40], c.rows), jnp.int32)
    top_p = jnp.asarray(np.resize([0.95, 1.0, 0.5, 0.95], c.rows),
                        jnp.float32)
    impl = "interpret" if c.interpret else "pallas"
    got, hlo = _run_compiled(
        lambda *a: topk_topp_mask(*a, impl=impl), logits, top_k, top_p)
    want = topk_topp_mask_ref(logits, top_k, top_p)
    keep_g, keep_w = got > NEG_INF / 2, want > NEG_INF / 2
    per_row = int(jnp.max(jnp.sum(keep_g != keep_w, axis=-1)))
    _require(bool(jnp.all(jnp.where(keep_g & keep_w, got == want, True))),
             "topk_topp_mask changed a kept logit")
    _report(f"topk_topp_mask V={V}", "keep_set_diff_per_row", per_row,
            MASK_TOL_ENTRIES, hlo, c)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServeConfig:
    arch: str = "qwen2-1.5b"
    smoke: bool = False
    requests: int = 8
    prompt_len: int = 512
    prefix_len: int = 384
    gen: int = 32
    slots: int = 8
    block_size: int = 16
    seed: int = 0


# arrivals per simulated second: the whole trace arrives within one step
ARRIVAL_RATE = 1000.0


class _StepRecorder:
    """Wraps a jitted fused step: lowers it with its first call's
    arguments and keeps the StableHLO text, then runs it unchanged."""

    def __init__(self, fn):
        self.fn = fn
        self.text = None

    def __call__(self, *args):
        if self.text is None:
            self.text = self.fn.lower(*args).as_text()
        return self.fn(*args)


def _pallas_kernels(text: str) -> set:
    return set(re.findall(r'kernel_name = "(\w+)"', text))


def _serve(cfg, params, c: ServeConfig, plan, sampling, *, record: bool):
    """One engine over the sysprompt trace: (results, trace, wall seconds,
    snapshot, {step variant: Pallas kernel names})."""
    eng = make_serving_engine(
        cfg, params, num_slots=c.slots, prompt_len=c.prompt_len,
        max_gen=c.gen, kv="paged", block_size=c.block_size,
        prefix_cache=True, plan=plan, clock=ManualClock())
    if record:
        eng.pool._decode = {s: _StepRecorder(f)
                            for s, f in eng.pool._decode.items()}
    trace = sysprompt_trace(c.requests, ARRIVAL_RATE,
                            prompt_len=c.prompt_len,
                            vocab_size=cfg.vocab_size,
                            prefix_len=c.prefix_len, gen_len=c.gen,
                            sampling=sampling, seed=c.seed)
    t0 = time.perf_counter()
    out = run_to_completion(eng, trace)
    jax.block_until_ready(eng.pool.caches)
    wall = time.perf_counter() - t0
    kernels = {}
    if record:
        kernels = {s: _pallas_kernels(r.text)
                   for s, r in eng.pool._decode.items() if r.text is not None}
    return out, trace, wall, eng.snapshot(), kernels


def _check_results(out, trace, vocab: int, tag: str) -> int:
    _require(len(out) == len(trace), f"{tag}: {len(out)}/{len(trace)} served")
    n = 0
    for r in trace:
        toks = out[r.rid]
        _require(len(toks) == r.eff_gen_len,
                 f"{tag}: request {r.rid} got {len(toks)}/{r.eff_gen_len}")
        _require(all(0 <= t < vocab for t in toks),
                 f"{tag}: request {r.rid} emitted an id outside the vocab")
        n += len(toks)
    return n


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def serve_phase(c: ServeConfig) -> None:
    cfg = _model(c.arch, c.smoke)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: Mo.init_params(k, cfg, Env(None, SERVE_PLAN)))(
        jax.random.PRNGKey(c.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"serve {cfg.name}: {n_params} params "
          f"({sum(x.nbytes for x in jax.tree.leaves(params))} bytes), "
          f"attn_impl={SERVE_PLAN.attn_impl}, init_s="
          f"{time.perf_counter() - t0!r}", flush=True)
    greedy = SamplingParams()
    seeded = SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                            seed=c.seed)
    # on the TPU the serving plan takes the Pallas paged kernel, and the
    # seeded steps also run the sampling kernel
    want_kernels = {False: {"_pd_kernel"}, True: {"_pd_kernel",
                                                   "_mask_kernel"}}
    expect_kernels = SERVE_PLAN.attn_impl == "pallas"
    outs = {}
    for tag, sampling in (("greedy", greedy), ("seeded", seeded)):
        out, trace, first_s, snap, kernels = _serve(
            cfg, params, c, SERVE_PLAN, sampling, record=True)
        n_tok = _check_results(out, trace, cfg.vocab_size, tag)
        again, _, second_s, _, _ = _serve(cfg, params, c, SERVE_PLAN,
                                          sampling, record=False)
        _require(again == out, f"{tag}: a second run emitted other tokens")
        print(f"serve {tag}: requests={len(out)}/{len(trace)} "
              f"tokens={n_tok} prefix_hit_rate="
              f"{snap.get('prefix_hit_rate', 0.0)!r} first_run_s={first_s!r} "
              f"second_run_s={second_s!r} compile_s="
              f"{first_s - second_s!r} peak_bytes_in_use={_peak_bytes()} "
              f"kernels={ {s: sorted(k) for s, k in kernels.items()} }",
              flush=True)
        if expect_kernels:
            for s, names in kernels.items():
                missing = want_kernels[s] - names
                _require(not missing, f"{tag}: step {s} lacks {missing}")
        outs[tag] = (out, trace)

    out, trace = outs["greedy"]
    naive = replace(SERVE_PLAN, attn_impl="naive")
    ref, _, naive_s, _, _ = _serve(cfg, params, c, naive, greedy,
                                   record=False)
    _check_results(ref, trace, cfg.vocab_size, "greedy naive")
    same = sum(int(a == b) for r in trace
               for a, b in zip(out[r.rid], ref[r.rid]))
    total = sum(len(out[r.rid]) for r in trace)
    print(f"serve greedy vs attn_impl=naive (information only): "
          f"token_agreement={same / total!r} ({same}/{total}) "
          f"naive_run_s={naive_s!r}", flush=True)


# ---------------------------------------------------------------------------
# elastic cluster (four chips)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ElasticConfig:
    arch: str = "paper-demo"
    smoke: bool = False
    seq_len: int = 512
    global_batch: int = 8
    seed: int = 0


# compute nodes (one device each) before and after the scale event, and
# training steps on each mesh
NODES_BEFORE, NODES_AFTER = 2, 4
STEPS_PER_MESH = 3


def _train_losses(cluster, cfg, shape, ckpt_dir, c: ElasticConfig, plan, *,
                  scale_at=None):
    """Train through ElasticTrainer; scale_at: (step, nodes) resize.
    Returns (losses, mesh sizes, oversubscribed flags, trainer stats)."""
    tr = ElasticTrainer(cluster.template, cfg, shape, ckpt_dir, plan=plan,
                        ckpt_every=1_000_000, seed=c.seed)
    losses, sizes, oversub = [], [], []
    for i in range(2 * STEPS_PER_MESH):
        if scale_at is not None and i == scale_at[0]:
            cluster.scale_to(scale_at[1])
        losses.append(tr.run_steps(1)["loss"])
        sizes.append(int(tr.env.mesh.devices.size))
        oversub.append(cluster.rendering.oversubscribed)
    tr.ckpt.wait()
    cluster.shutdown()
    return losses, sizes, oversub, tr.stats


def elastic_phase(c: ElasticConfig) -> None:
    _require(len(jax.devices()) >= NODES_AFTER,
             f"needs {NODES_AFTER} devices, found {len(jax.devices())}")
    cfg = _model(c.arch, c.smoke)
    shape = ShapeConfig("elastic", c.seq_len, c.global_batch, "train")
    plan = ParallelPlan(fsdp=False, remat="nothing", attn_impl="naive",
                        kv_cache="replicated")
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        losses, sizes, oversub, stats = _train_losses(
            VirtualCluster(n_compute=NODES_BEFORE), cfg, shape,
            os.path.join(d, "elastic"), c, plan,
            scale_at=(STEPS_PER_MESH, NODES_AFTER))
        elastic_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref, ref_sizes, _, _ = _train_losses(
            VirtualCluster(n_compute=1), cfg, shape, os.path.join(d, "ref"),
            c, plan)
        ref_s = time.perf_counter() - t0
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
    for i, (a, b, r, n) in enumerate(zip(losses, ref, rel, sizes)):
        print(f"elastic step {i + 1}: devices={n} loss={a!r} "
              f"one_chip_loss={b!r} rel_diff={r!r}", flush=True)
    print(f"elastic {cfg.name}: reshards={stats.reshards} "
          f"epoch_changes={stats.epoch_changes} "
          f"steps_lost={stats.steps_lost} oversubscribed={any(oversub)} "
          f"elastic_run_s={elastic_s!r} one_chip_run_s={ref_s!r}",
          flush=True)
    want = [NODES_BEFORE] * STEPS_PER_MESH + [NODES_AFTER] * STEPS_PER_MESH
    _require(sizes == want, f"mesh sizes {sizes}, expected {want}")
    _require(ref_sizes == [1] * len(ref_sizes), ref_sizes)
    _require(not any(oversub), "the cluster rendered an oversubscribed mesh")
    _require(stats.reshards >= 1 and stats.steps_lost == 0, stats)
    _require(max(rel) <= LOSS_RTOL,
             f"loss parity {max(rel)} above {LOSS_RTOL}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the elastic 2 -> 4 node training phase "
                    "(needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.four_chip:
        elastic_phase(ElasticConfig(seed=args.seed))
    else:
        kernel_phase(KernelConfig(seed=args.seed))
        print(f"phase kernels: wall_s={time.perf_counter() - t0!r}",
              flush=True)
        t1 = time.perf_counter()
        serve_phase(ServeConfig(seed=args.seed))
        print(f"phase serve: wall_s={time.perf_counter() - t1!r}", flush=True)
    print(f"total wall_s={time.perf_counter() - t0!r}", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
