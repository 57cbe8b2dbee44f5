"""ReplicaEngine + ServingEngine — continuous batching over a KVBackend.

The serving data plane is split into two layers:

`ReplicaEngine` is ONE serving replica: a KVBackend (its own block pool,
its own prefix cache), the in-flight lane/slot bookkeeping, the fused
decode step, and its own ServingMetrics. It has no arrival queue and no
SchedulerPolicy — it only answers "can you take this request?"
(`can_accept`), commits admissions (`admit`), and runs decode ticks
(`step_decode`). It also owns the drain lifecycle real scale-down needs:
a draining replica accepts no new work, finishes (or restart-preempts)
what it holds, and `release()` returns its pool with leak checking.

`ServingEngine` is the single-replica composition kept as the stable
public surface: a RequestQueue + SchedulerPolicy admission loop over one
ReplicaEngine. The multi-replica composition is `serve/router.py`'s
`ReplicaSet`: a Router front-end owning the global queue, admitting each
request to one of N ReplicaEngines via a RoutingPolicy.

One scheduler iteration (ServingEngine.step()):

  1. admit: the SchedulerPolicy (serve/policy.py) picks which arrived
     request admits next (FIFO, EDF, ...) while the KVBackend can reserve
     its worst case (exhaustion = queue backpressure, not an OOM
     mid-decode). If the backend is full, the policy may issue a
     preemption verdict: the engine evicts the victim, clears its
     progress, and re-queues it at its original arrival time —
     restart-preemption is safe because sampling is position-keyed
     (serve/sampling.py), so the victim regenerates identical tokens.
     On chunk-capable backends the prompt is *not* prefilled in a separate
     batch-1 call: it streams through `prefill_chunk` piggybacked lane
     rows of the regular decode step (chunked prefill). Other admissions
     take classic batch-1 prefill + insert.
  2. decode: one fused jitted step over decode rows (+ lane rows), run by
     the backend (it owns the cache layout and the step function): every
     row writes K/V where its backend says and attends at its own depth;
     the sample step (per-request temperature / top-k / top-p, seeded
     per-position PRNG; temperature=0 = argmax) happens on device and the
     [T] int32 token vector is the only per-step host download.
  3. retire: finished slots (gen budget spent, or a stop token emitted)
     return their capacity to the backend.

Each iteration is recorded as spans (core/spans.py) on the engine's
clock: serve.step over serve.admit, serve.decode (serve.pack,
serve.dispatch, serve.device_wait, serve.retire) and serve.snapshot.

The engine never re-jits per admission; step shapes are pinned to
(num_slots,) and (num_slots + prefill_chunk,) rows. Greedy decoding keeps
output token-for-token equal to the one-shot serve_batch baseline on every
backend; seeded sampling is reproducible and lane-placement-invariant —
tests/test_serving.py holds all of it. Every row is computed independently
(each attends over its own KV at its own depth), which is what makes
per-request output invariant to *which replica* serves it — the property
the multi-replica exactness tests pin down.

The clock is injected: tests and the simulated cluster drive a ManualClock
(deterministic arrival replay); nothing here sleeps.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelPlan
from repro.core.clock import Clock, ManualClock
from repro.core.spans import compile_seconds, default_recorder
from repro.launch import steps as St
from repro.models.env import Env
from repro.serve.kv import KVBackend, make_kv_backend, shared_jit
from repro.serve.metrics import ServingMetrics
from repro.serve.policy import FIFOPolicy, SchedulerPolicy
from repro.serve.request import Request, RequestQueue

Pytree = Any


def _default_attn_impl() -> str:
    """Pallas paged flash-decode on TPU; vectorized XLA gather elsewhere.
    Same math, different rounding: the kernel keeps scores and
    probabilities in f32 where the gather path rounds them to bf16, so a
    greedy token can differ between the two at a bf16 near-tie."""
    return "pallas" if jax.default_backend() == "tpu" else "naive"


SERVE_PLAN = ParallelPlan(fsdp=False, remat="full",
                          attn_impl=_default_attn_impl(),
                          kv_cache="replicated")


@dataclass
class _Lane:
    """An in-flight chunked prefill riding the decode batch's lane rows.

    prefill_chunk is a *token budget* shared by every admitting request
    (Sarathi-style): each step the budget rows are packed FIFO across the
    open lanes, so several short prompts can prefill in one step while a
    long prompt streams through in chunks."""
    slot: int
    req: Request
    pos: int = 0  # prompt tokens consumed so far
    take: int = 0  # rows granted this step
    last_row: int = 0  # row of the chunk's final token (first-token source)


@dataclass
class _Batch:
    """One packed step: the meta rows the fused step reads, the drafts
    its verify rows carry, and its row and grid counts."""
    meta_i: np.ndarray
    meta_f: np.ndarray
    row_slots: np.ndarray
    sample: bool
    drafts: Dict[int, List[int]]
    spec_base: int
    counts: Dict[str, int]


class ReplicaEngine:
    """One serving replica: KVBackend + lanes + fused step + metrics.

    Admission *order* lives above this class (ServingEngine's policy loop
    for one replica; ReplicaSet's router for a fleet); the replica only
    commits admissions it has capacity for and steps its own batch."""

    def __init__(self, cfg: ModelConfig, params: Pytree, *,
                 name: str = "replica-0",
                 num_slots: int = 4, prompt_len: int = 32, max_gen: int = 32,
                 kv="paged", block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 max_shared_fraction: float = 1.0,
                 prefill_chunk: Optional[int] = None,
                 spec=None, spec_k=4,
                 swap: bool = False,
                 swap_budget_blocks: Optional[int] = None,
                 swap_pool=None,
                 plan: Optional[ParallelPlan] = None, mesh=None,
                 clock: Optional[Clock] = None,
                 metrics_window_s: float = 10.0):
        self.cfg = cfg
        self.params = params
        self.name = name
        self.prompt_len = prompt_len
        self.max_gen = max_gen
        self.clock = clock or ManualClock()
        self.spans = default_recorder()
        env = Env(mesh=mesh, plan=plan or SERVE_PLAN)
        self.env = env
        if isinstance(kv, str):
            self.pool: KVBackend = make_kv_backend(
                kv, cfg, env, num_slots=num_slots, prompt_len=prompt_len,
                max_gen=max_gen, block_size=block_size, kv_blocks=kv_blocks,
                prefix_cache=prefix_cache,
                max_shared_fraction=max_shared_fraction,
                swap=swap, swap_budget_blocks=swap_budget_blocks,
                swap_pool=swap_pool)
        else:  # a pre-built backend (custom implementations plug in here)
            self.pool = kv
            num_slots = self.pool.num_slots
        self.kv = self.pool.kind
        if prefill_chunk is None:
            prefill_chunk = prompt_len if self.pool.chunk_prefill_ok else 0
        if prefill_chunk and not self.pool.chunk_prefill_ok:
            raise ValueError(
                f"{cfg.name}: chunked prefill is not supported by the "
                f"'{self.pool.kind}' backend for this arch (recurrent "
                "state is sequential over the prompt; ring writes wrap "
                "within a chunk; the slot pool has no per-row tables)")
        self.prefill_chunk = int(prefill_chunk)
        # -- speculative decoding (serve/spec.py) --------------------------
        # verify rows ride the step like lane rows: several rows share one
        # slot at consecutive depths. That needs per-row independent math
        # over a scatter-then-write cache — attention blocks only. Window
        # ('local') rings wrap within a draft run and recurrent state is
        # sequential, so both are gated off (exactly the chunked-prefill
        # gate, for the same reason).
        # spec_k="auto": the verify-row block stays `cap` rows wide (step
        # shapes are pinned) but the live draft depth per request is tuned
        # from its own acceptance feedback (serve/spec.py AdaptiveSpecK)
        if spec_k == "auto":
            from repro.serve.spec import AdaptiveSpecK
            self.spec_k = 4
            self._spec_ctl: Optional[Any] = AdaptiveSpecK(cap=self.spec_k)
        else:
            self.spec_k = int(spec_k)
            self._spec_ctl = None
        if isinstance(spec, str) or spec is None:
            from repro.serve.spec import make_drafter
            self.drafter = make_drafter(spec, cfg, env,
                                        num_slots=num_slots,
                                        prompt_len=prompt_len,
                                        max_gen=max_gen, spec_k=self.spec_k)
        else:  # a pre-built Drafter (tests plug deterministic ones in)
            self.drafter = spec
        if self.drafter is not None:
            kinds = set(cfg.block_pattern) | set(cfg.pattern_tail)
            if not kinds <= {"attn", "moe"}:
                raise ValueError(
                    f"{cfg.name}: speculative decoding needs per-row "
                    "attention blocks (sliding-window rings wrap within a "
                    "draft; recurrent state is sequential)")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.metrics = ServingMetrics(window_s=metrics_window_s)
        self._prefill = shared_jit(
            ("prefill", cfg, env.plan, env.mesh),
            lambda: St.make_prefill_step(cfg, env))
        # classic admissions sample their first token from the prefill
        # logits with the same fused sample math (position 0)
        self._sample_first = shared_jit(
            ("sample_first", cfg, prompt_len),
            lambda: St.make_sample_fn(cfg, prompt_len))
        self._lanes: List[_Lane] = []
        # device [T] int32: last step's fused sample/argmax. Seeded at
        # num_slots so the step's (rows, prev-rows) shape pair cycles
        # through its <= 4 combinations deterministically — a two-request
        # warm trace compiles them all (benchmarks warm exactly that way).
        self._tok_prev = jnp.zeros((num_slots,), jnp.int32)
        self._row_src: Dict[int, int] = {}  # slot -> row in _tok_prev
        self._fresh: Dict[int, int] = {}  # slot -> host-known next token
        self._inflight: Dict[int, Request] = {}  # rid -> request
        self.completed: List[Request] = []
        self.decode_steps = 0
        self.draining = False
        # host-side gauges merged into every snapshot (a rollout loop
        # publishes its phase metrics here so they ride the same rollup
        # the autoscaler already reads)
        self.extra_metrics: Dict[str, float] = {}

    # -- state -----------------------------------------------------------------
    @property
    def busy(self) -> bool:
        return bool(self._inflight)

    def prompt_arg(self, req: Request):
        """The prompt to hand the backend's admission probes: chunked
        admissions pass it so a prefix-caching backend can attach shared
        blocks (classic batch-1 prefill scatters the whole prompt and
        cannot share)."""
        return req.prompt if self.prefill_chunk else None

    def admission_room(self) -> bool:
        """Lane-budget gate: open lanes only while the step's token budget
        can still reach a new prompt (bounds admitted-but-starved lanes
        ~1). Classic (non-chunked) replicas always have room — the
        backend's can_admit is the only gate."""
        if not self.prefill_chunk:
            return True
        return (sum(len(l.req.prompt) - l.pos for l in self._lanes)
                < self.prefill_chunk)

    def can_take(self, req: Request) -> bool:
        """Capacity predicate only: can the backend hold `req` right now?
        A swapped-out request resumes instead of re-admitting — its gate
        is can_resume (free slot + its allocated blocks + its unspent
        reservation), not the fresh-admission math."""
        if self.pool.has_swapped(req.rid):
            return self.pool.can_resume(req.rid)
        return self.pool.can_admit(req.eff_gen_len,
                                   prompt=self.prompt_arg(req))

    def can_accept(self, req: Request) -> bool:
        """Could this replica commit `req` right now? (Routing predicate —
        admission-accurate because admit() takes its reservations
        immediately, so successive calls within one tick stay honest.)"""
        return (not self.draining and self.admission_room()
                and self.can_take(req))

    # -- admission commit ---------------------------------------------------
    def admit(self, req: Request, now: float) -> None:
        """Commit one admission (caller already took it off its queue)."""
        req.t_admit = now
        self._inflight[req.rid] = req
        if self.pool.has_swapped(req.rid):
            # swap-in resume: the host tier holds the request's whole KV
            # at its preemption cursor. Restore it, seed the fused step
            # with the last emitted token (the one swap-out never fed
            # back), and decoding continues bit-identically — no prefill,
            # no recompute, first token long since recorded.
            slot = self.pool.swap_in(req.rid)
            self._fresh[slot] = req.tokens[-1]
            if self.drafter is not None:
                self.drafter.admit(req)
            return
        if self.drafter is not None:
            self.drafter.admit(req)
        # stamped by the first step that carries one of its lane rows;
        # a classic admission prefills here and now
        req.t_lane_start = None if self.prefill_chunk else now
        if self.prefill_chunk:
            slot = self.pool.admit(req.rid, req.eff_gen_len,
                                   prefilling=True, prompt=req.prompt)
            # cached prefix positions never ride a lane: start at the
            # first uncached token (at most prompt_len - 1 — the last
            # prompt token always runs to emit the first token)
            self._lanes.append(_Lane(
                slot=slot, req=req,
                pos=self.pool.cached_prefix_len(slot)))
        else:
            self._admit_classic(
                self.pool.admit(req.rid, req.eff_gen_len), req)

    def _admit_classic(self, slot: int, req: Request) -> None:
        """Batch-1 prefill + cache insert (the non-chunked path). The first
        token is sampled from the prefill logits at position 0 — greedy
        requests take the plain argmax, bit-identical to the pre-v2 engine
        — and fed to the same step's decode via the fresh-token path. It
        is stamped when its transfer to the host ends."""
        logits, caches = self._prefill(
            self.params, {"tokens": jnp.asarray(req.prompt)[None]})
        self.metrics.record_prefill_tokens(len(req.prompt),
                                           recompute=req.restarts > 0)
        self.pool.insert(slot, req.rid, caches, req.eff_gen_len)
        if req.sampling.greedy:
            first = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
        else:
            mi = np.zeros((St.META_I_ROWS, 1), np.int32)
            mf = np.zeros((St.META_F_ROWS, 1), np.float32)
            mi[St.ROW_CUR_LEN, 0] = len(req.prompt) - 1  # -> position 0
            self._fill_sampling(mi, mf, 0, req)
            first = int(self._sample_first(logits, mi, mf)[0])
        t = self.clock.now()  # the first token's transfer has ended
        req.t_first_token = t
        req.tokens.append(first)
        self._fresh[slot] = first
        self.metrics.record_first_token(req, t)
        self.metrics.record_tokens(t, 1)
        if self.pool.finished(slot) or first in req.sampling.stop_set:
            self._retire(slot, t)  # gen_len == 1 / instant stop token

    # -- preemption (restart-style) ------------------------------------------
    def running(self) -> List[Request]:
        """Decoding (preemptible) requests, for the policy's verdict."""
        return [self._inflight[self.pool.info(s).rid]
                for s in self.pool.active_slots()]

    def slot_of(self, req: Request) -> Optional[int]:
        """The slot `req` occupies, or None if it holds none (a stale
        policy verdict — e.g. the victim retired this iteration). Callers
        treat None as "no victim"; a bare next() here would leak
        StopIteration out of the scheduler loop."""
        return next((s for s in self.pool.occupied_slots()
                     if self.pool.rid_of(s) == req.rid), None)

    def lane_open(self, slot: int) -> bool:
        return any(ln.slot == slot for ln in self._lanes)

    def preempt(self, victim: Request, slot: int, now: float) -> Request:
        """Preemption: return the victim's KV capacity; the caller
        re-queues it at its original arrival time.

        Swap-out first: a backend with a host tier copies the victim's
        blocks out (serve/blocks.py HostSwapPool), so its tokens and
        first-token timestamp survive — re-admission restores the KV and
        decoding resumes bit-identically with zero recompute.

        Restart fallback (no host tier / budget full / mid-prefill):
        clear the victim's progress entirely. Safe because sampling is
        position-keyed — on re-admission the victim regenerates
        bit-identical tokens (greedy or seeded) — but the re-prefill is
        paid compute, booked into recomputed_tokens via `restarts`.

        Metrics semantics: the victim's pre-preemption tokens stay in
        tokens_per_s (the device really decoded them — that is the decode
        throughput the autoscaler budgets), and a restart records a
        second, longer TTFT sample alongside the first. Both read as load,
        i.e. they bias the policies toward scaling up while preemptions
        are happening — the conservative direction."""
        # only decode slots are preemptible (running() excludes
        # prefilling): an open lane would keep writing prompt chunks into
        # a freed/reassigned slot — make the invariant explicit here too
        assert not self.lane_open(slot), \
            f"preempting slot {slot} with an open prefill lane"
        swapped = self.pool.swap_out(slot)
        if not swapped:
            self.pool.evict(slot)
        self._row_src.pop(slot, None)
        self._fresh.pop(slot, None)
        if self.drafter is not None:
            self.drafter.retire(victim.rid)
        if self._spec_ctl is not None:
            self._spec_ctl.retire(victim.rid)
        del self._inflight[victim.rid]
        victim.t_admit = None
        if not swapped:
            victim.tokens.clear()
            victim.t_lane_start = None
            victim.t_first_token = None
            victim.restarts += 1
        self.metrics.record_preempt(now)
        return victim

    # -- drain lifecycle ------------------------------------------------------
    def start_drain(self, *, preempt: bool = False) -> List[Request]:
        """Enter drain mode: no new admissions (can_accept goes False).
        With preempt=False the replica finishes what it holds; with
        preempt=True every in-flight request — decoding or mid-prefill —
        is restart-preempted and returned for the caller to re-queue
        (bit-identical regeneration is the position-keyed sampling
        guarantee, so a drain can be immediate without changing output)."""
        self.draining = True
        # a draining replica will never run its planned swap-ins — free
        # the standing reservations so a live peer can take them over
        self.pool.cancel_resume_plans()
        if not preempt:
            return []
        now = self.clock.now()
        # closing the lanes first makes mid-prefill slots preemptible too
        # (preempt()'s open-lane guard is about a lane writing into a
        # freed slot; with no lanes left there is nothing to write)
        self._lanes.clear()
        return [self.preempt(self._inflight[self.pool.rid_of(slot)], slot,
                             now)
                for slot in list(self.pool.occupied_slots())]

    def cancel_drain(self) -> None:
        """Scale-up may reuse a still-draining replica: its cache is warm,
        which beats a cold spawn."""
        self.draining = False

    def release(self) -> None:
        """Return the replica's pool to the void. Must be idle; the
        backend's release() verifies its free-list accounting returns to
        empty (no leaked blocks/reservations) before dropping the device
        cache."""
        if self.busy or self._lanes:
            raise RuntimeError(
                f"{self.name}: release() while {len(self._inflight)} "
                "requests are in flight — drain first")
        self.pool.release()

    # -- one decode tick -----------------------------------------------------
    def step_decode(self, now: float) -> int:
        """Run one fused decode step over the replica's mixed batch
        (+ prefill lanes) and retire finished requests. Returns tokens
        emitted this tick.

        Recorded as a `serve.decode` span whose children split the tick:
        serve.pack (rows, meta, block growth), serve.dispatch (the
        backend's table build, uploads and jitted call), serve.device_wait
        (the one transfer) and serve.retire; the step's row and grid
        counts are its attributes. Tokens are stamped when the transfer
        that brought them ends, not at `now` (the iteration's start)."""
        active = self.pool.active_slots()
        lanes = self._lanes
        if not active and not lanes:
            return 0
        spans, clock = self.spans, self.clock
        with spans.span("serve.decode", clock) as dec:
            with spans.span("serve.pack", clock):
                b = self._pack(active, lanes)
            dec.attrs = b.counts
            with spans.span("serve.dispatch", clock) as disp:
                for lane in lanes:
                    if lane.take > 0 and lane.req.t_lane_start is None:
                        lane.req.t_lane_start = disp.start
                nxt_dev = self.pool.decode(self.params, self._tok_prev,
                                           b.meta_i, b.meta_f, b.row_slots,
                                           sample=b.sample)
                self._tok_prev = nxt_dev
            with spans.span("serve.device_wait", clock) as wait:
                nxt = np.asarray(nxt_dev)  # the one host transfer per step
            self.decode_steps += 1
            with spans.span("serve.retire", clock):
                return self._emit(active, lanes, b, nxt, wait.end)

    def _pack(self, active: List[int], lanes: List[_Lane]) -> "_Batch":
        """Pack one step's rows: decode rows at their slots, then the
        prefill lanes' token budget, then the verify rows; grow each
        slot's KV to cover the positions its rows write."""
        # pack the prefill token budget FIFO across open lanes
        N = self.pool.num_slots
        budget = self.prefill_chunk
        for lane in lanes:
            lane.take = min(budget, len(lane.req.prompt) - lane.pos)
            budget -= lane.take
        # prefill compute actually spent this step (prefix-cache hits
        # shrink it: cached positions never occupy a lane row); chunks of
        # restart-preempted requests are re-work, booked separately
        self.metrics.record_prefill_tokens(
            sum(ln.take for ln in lanes if ln.req.restarts == 0))
        self.metrics.record_prefill_tokens(
            sum(ln.take for ln in lanes if ln.req.restarts > 0),
            recompute=True)
        lane_rows = self.prefill_chunk if lanes else 0
        # speculative verify rows: a fixed block of num_slots * spec_k rows
        # stacked after the lane rows (slot s's candidates at spec_base +
        # s*spec_k + j), present whenever a drafter is configured so the
        # step shape set stays as bounded as without speculation. Unused
        # candidate rows stay masked (row_slots -1).
        spec_rows = N * self.spec_k if self.drafter is not None else 0
        spec_base = N + lane_rows
        T = N + lane_rows + spec_rows
        meta_i = np.zeros((St.META_I_ROWS, T), np.int32)
        meta_f = np.zeros((St.META_F_ROWS, T), np.float32)
        meta_i[St.ROW_TOK_SRC, :] = -1
        row_slots = np.full((T,), -1, np.int32)
        sample = False
        # draft proposals per decoding slot. k is capped so the last
        # verify row's write position stays inside the request's declared
        # budget: max accepted emission is gen_len - tokens_done tokens,
        # i.e. the final-step row never speculates (its write position
        # prompt_len + gen_len - 2 is the last the reservation covers).
        drafts: Dict[int, List[int]] = {}
        if self.drafter is not None:
            for slot in active:
                info = self.pool.info(slot)
                req = self._inflight[info.rid]
                k_live = (self.spec_k if self._spec_ctl is None
                          else self._spec_ctl.k(req.rid))
                k_eff = min(k_live,
                            info.gen_len - info.tokens_done - 1)
                if k_eff <= 0:
                    continue
                d = self.drafter.propose(req, k_eff)[:k_eff]
                if d:
                    drafts[slot] = d
        for slot in active:
            info = self.pool.info(slot)
            req = self._inflight[info.rid]
            self.pool.ensure(slot, info.cur_len)
            row_slots[slot] = slot
            meta_i[St.ROW_CUR_LEN, slot] = info.cur_len
            sample |= self._fill_sampling(meta_i, meta_f, slot, req)
            if slot in self._fresh:
                meta_i[St.ROW_TOK_SRC, slot] = -1
                meta_i[St.ROW_FRESH, slot] = self._fresh.pop(slot)
            else:
                meta_i[St.ROW_TOK_SRC, slot] = self._row_src.pop(slot, slot)
        for slot, d in drafts.items():
            info = self.pool.info(slot)
            req = self._inflight[info.rid]
            # blocks for every candidate write position (rolled back on
            # rejection via truncate)
            self.pool.ensure(slot, info.cur_len + len(d))
            base = spec_base + slot * self.spec_k
            for j, tok in enumerate(d):
                r = base + j
                row_slots[r] = slot
                meta_i[St.ROW_FRESH, r] = tok
                meta_i[St.ROW_CUR_LEN, r] = info.cur_len + 1 + j
                sample |= self._fill_sampling(meta_i, meta_f, r, req)
        row = N
        for lane in lanes:
            if lane.take <= 0:
                continue
            self.pool.ensure(lane.slot, lane.pos + lane.take - 1)
            sl = slice(row, row + lane.take)
            meta_i[St.ROW_FRESH, sl] = \
                lane.req.prompt[lane.pos:lane.pos + lane.take]
            meta_i[St.ROW_CUR_LEN, sl] = \
                np.arange(lane.pos, lane.pos + lane.take)
            row_slots[sl] = lane.slot
            sample |= self._fill_sampling(meta_i, meta_f, sl, lane.req)
            row += lane.take
            lane.last_row = row - 1
        # real rows: decode rows, lane rows with a take, verify rows that
        # carry a draft; every other row is padding the step still runs
        depths = meta_i[St.ROW_CUR_LEN][row_slots >= 0]
        counts = {"rows": T, "real_rows": len(depths)}
        grid = self.pool.kernel_grid(T, depths)
        if grid is not None:
            counts["grid_cells"], counts["grid_live"] = grid
        return _Batch(meta_i, meta_f, row_slots, sample, drafts, spec_base,
                      counts)

    def _emit(self, active: List[int], lanes: List[_Lane], b: "_Batch",
              nxt: np.ndarray, t: float) -> int:
        """Hand the step's tokens to their requests (stamped at t), close
        the lanes whose prompt is done, retire finished requests."""
        drafts, spec_base = b.drafts, b.spec_base
        emitted = 0
        for slot in active:
            info = self.pool.info(slot)
            req = self._inflight[info.rid]
            cur = info.cur_len
            d = drafts.get(slot, [])
            outs = [int(nxt[slot])]
            base = spec_base + slot * self.spec_k
            outs += [int(nxt[base + j]) for j in range(len(d))]
            # accept the longest prefix where draft j matches the target's
            # own output for that position (o_{j-1}): verify row j's
            # logits — and, seeded, its fold_in(seed, position) draw — are
            # bit-identical to sequential decode's exactly while every
            # earlier draft matched, so emitting o_0..o_a is bit-exact
            a = 0
            while a < len(d) and d[a] == outs[a]:
                a += 1
            emit = outs[:a + 1]
            stop = req.sampling.stop_set
            cut = next((i for i, x in enumerate(emit) if x in stop), None)
            if cut is not None:
                emit = emit[:cut + 1]
            if d:
                # roll the rejected suffix's KV capacity back (a no-op
                # when every draft was accepted) and record acceptance
                self.pool.truncate(slot, cur + len(emit))
                self.metrics.record_spec(len(d), len(emit) - 1, len(emit))
                if self._spec_ctl is not None:
                    self._spec_ctl.update(req.rid, len(d), len(emit) - 1)
            # next step's input token (the last emitted) sits at the row
            # that produced it — main row for a=0, else verify row a-1
            self._row_src[slot] = (slot if len(emit) == 1
                                   else base + len(emit) - 2)
            for tok in emit:
                self.pool.advance(slot)
                req.tokens.append(tok)
                emitted += 1
            if self.pool.finished(slot) or emit[-1] in stop:
                self._retire(slot, t)
        still_open: List[_Lane] = []
        for lane in lanes:
            lane.pos += lane.take
            if lane.pos < len(lane.req.prompt):
                still_open.append(lane)
                continue
            slot = lane.slot
            self.pool.finish_prefill(slot)
            req = lane.req
            req.t_first_token = t
            tok = int(nxt[lane.last_row])
            req.tokens.append(tok)
            self.metrics.record_first_token(req, t)
            # next step, this slot's input token comes from the lane row
            self._row_src[slot] = lane.last_row
            emitted += 1
            if self.pool.finished(slot) or tok in req.sampling.stop_set:
                self._retire(slot, t)
        self._lanes = still_open
        if emitted:
            self.metrics.record_tokens(t, emitted)
        return emitted

    @staticmethod
    def _fill_sampling(meta_i, meta_f, rows, req: Request) -> bool:
        """Write one request's SamplingParams into its row(s); returns
        whether the row actually samples (so an all-greedy batch can take
        the pure-argmax step variant)."""
        sp = req.sampling
        meta_i[St.ROW_SEED, rows] = sp.seed
        meta_i[St.ROW_TOP_K, rows] = sp.top_k
        meta_i[St.ROW_POS0, rows] = len(req.prompt) - 1
        meta_f[St.ROW_TEMPERATURE, rows] = sp.temperature
        meta_f[St.ROW_TOP_P, rows] = sp.top_p
        return not sp.greedy

    def _retire(self, slot: int, now: float) -> None:
        rid = self.pool.rid_of(slot)
        req = self._inflight.pop(rid)
        req.t_done = now
        self.completed.append(req)
        self.metrics.record_done(req, now)
        self.pool.evict(slot)
        self._row_src.pop(slot, None)
        self._fresh.pop(slot, None)
        if self.drafter is not None:
            self.drafter.retire(rid)
        if self._spec_ctl is not None:
            self._spec_ctl.retire(rid)

    # -- reporting ----------------------------------------------------------------
    def load_score(self):
        """Routing key: committed KV first (the signal that actually gates
        admission on paged backends; slot occupancy elsewhere), the
        in-flight count as the queue-depth tiebreak, then *absolute* free
        capacity. The fractions alone mis-rank heterogeneous fleets: two
        empty replicas with unequal --kv-blocks both score occupancy 0.0,
        but the big pool can absorb strictly more load — prefer it (more
        free capacity = smaller key). Homogeneous fleets are unaffected:
        equal fractions imply equal free capacity, so the ordering
        degenerates to the old one."""
        m = self.pool.metrics()
        return (m.get("kv_block_occupancy", self.pool.occupancy),
                len(self._inflight), -self.pool.free_capacity)

    def set_params(self, params: Pytree) -> None:
        """Swap the serving weights (a post-training loop publishing its
        updated policy). Only between requests: in-flight KV was computed
        under the old weights, so a mid-request swap would silently mix
        models inside one generation. Same tree structure as the old
        params keeps every shared jit warm — no recompile."""
        if self.busy or self._lanes:
            raise RuntimeError(
                f"{self.name}: set_params with {len(self._inflight)} "
                "requests in flight — drain first")
        self.params = params

    def snapshot(self, *, queue_depth: Optional[int] = None
                 ) -> Dict[str, float]:
        return self.metrics.snapshot(self.clock.now(),
                                     queue_depth=queue_depth,
                                     slot_occupancy=self.pool.occupancy,
                                     **self.pool.metrics(),
                                     **self.extra_metrics)


class ServingEngine:
    """The single-replica serving composition: RequestQueue +
    SchedulerPolicy admission loop over one ReplicaEngine. Kept as the
    stable public surface (tests/CLI/benchmarks); `serve/router.py`'s
    ReplicaSet is the N-replica composition of the same pieces."""

    def __init__(self, cfg: ModelConfig, params: Pytree, *,
                 num_slots: int = 4, prompt_len: int = 32, max_gen: int = 32,
                 kv="paged", block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 max_shared_fraction: float = 1.0,
                 prefill_chunk: Optional[int] = None,
                 spec=None, spec_k=4,
                 swap: bool = False,
                 swap_budget_blocks: Optional[int] = None,
                 swap_pool=None,
                 policy: Optional[SchedulerPolicy] = None,
                 plan: Optional[ParallelPlan] = None, mesh=None,
                 clock: Optional[Clock] = None,
                 metrics_window_s: float = 10.0):
        self.replica = ReplicaEngine(
            cfg, params, num_slots=num_slots, prompt_len=prompt_len,
            max_gen=max_gen, kv=kv, block_size=block_size,
            kv_blocks=kv_blocks, prefix_cache=prefix_cache,
            max_shared_fraction=max_shared_fraction,
            prefill_chunk=prefill_chunk, spec=spec, spec_k=spec_k,
            swap=swap, swap_budget_blocks=swap_budget_blocks,
            swap_pool=swap_pool,
            plan=plan, mesh=mesh, clock=clock,
            metrics_window_s=metrics_window_s)
        self.policy: SchedulerPolicy = policy or FIFOPolicy()
        self.queue = RequestQueue()

    # -- delegated surface (the replica owns the data plane) -----------------
    @property
    def cfg(self):
        return self.replica.cfg

    @property
    def params(self):
        return self.replica.params

    @property
    def env(self):
        return self.replica.env

    @property
    def clock(self):
        return self.replica.clock

    @property
    def pool(self) -> KVBackend:
        return self.replica.pool

    @property
    def kv(self) -> str:
        return self.replica.kv

    @property
    def prompt_len(self) -> int:
        return self.replica.prompt_len

    @property
    def max_gen(self) -> int:
        return self.replica.max_gen

    @property
    def prefill_chunk(self) -> int:
        return self.replica.prefill_chunk

    @property
    def drafter(self):
        return self.replica.drafter

    @property
    def spec_k(self) -> int:
        return self.replica.spec_k

    @property
    def metrics(self) -> ServingMetrics:
        return self.replica.metrics

    @metrics.setter
    def metrics(self, m: ServingMetrics) -> None:
        self.replica.metrics = m

    @property
    def completed(self) -> List[Request]:
        return self.replica.completed

    @property
    def decode_steps(self) -> int:
        return self.replica.decode_steps

    @decode_steps.setter
    def decode_steps(self, n: int) -> None:
        self.replica.decode_steps = n

    @property
    def extra_metrics(self) -> Dict[str, float]:
        return self.replica.extra_metrics

    def set_params(self, params: Pytree) -> None:
        self.replica.set_params(params)

    @property
    def _prefill(self):
        return self.replica._prefill

    @property
    def _lanes(self) -> List[_Lane]:
        return self.replica._lanes

    @property
    def _inflight(self) -> Dict[int, Request]:
        return self.replica._inflight

    # -- state -----------------------------------------------------------------
    @property
    def busy(self) -> bool:
        return self.replica.busy

    def pending(self) -> int:
        return len(self.queue)

    def drained(self) -> bool:
        return not self.busy and not self.pending()

    def submit(self, requests: Sequence[Request]) -> None:
        """Validate and enqueue. Never mutates the caller's Requests: the
        admitted generation budget (gen_len capped by max_tokens) is
        derived at admission via Request.eff_gen_len, so re-submitting the
        same objects (the CLI --verify re-serve path) sees the declared
        gen_len unchanged."""
        validate_requests(requests, self.prompt_len, self.max_gen,
                          allow_shorter=self.prefill_chunk > 0)
        for r in requests:
            self.queue.push(r)

    # -- scheduler iteration ------------------------------------------------------
    def step(self) -> Dict[str, float]:
        """Admit arrivals (policy order), run one fused decode step over
        the mixed batch (+ prefill lanes), retire finished requests.
        Returns the metrics snapshot (what a node would publish).
        Recorded as a `serve.step` span (attribute compile_s: seconds JAX
        spent tracing and compiling inside it) over serve.admit,
        serve.decode and serve.snapshot."""
        spans, clock = self.replica.spans, self.clock
        with spans.span("serve.step", clock) as st:
            c0 = compile_seconds()
            with spans.span("serve.admit", clock):
                self._admit_ready(st.start)
            self.replica.step_decode(st.start)
            with spans.span("serve.snapshot", clock):
                snap = self.snapshot()
            st.attrs = {"compile_s": compile_seconds() - c0}
        return snap

    # -- admission ----------------------------------------------------------------
    def _admit_ready(self, now: float) -> None:
        rep = self.replica
        # swap-aware admission: before any fresh request can claim blocks,
        # every arrived swapped-out victim gets a re-admission *plan* — a
        # standing reservation for its resume footprint (blocks it held +
        # its unspent reservation). Fresh admissions see the shrunk
        # free_unreserved and queue behind the victim instead of starving
        # it; the plan is consumed by swap_in and survives across ticks,
        # so resume capacity accretes instead of being re-raced each step.
        for r in self.queue.ready(now):
            if rep.pool.has_swapped(r.rid):
                rep.pool.plan_resume(r.rid)
        preempted = False  # at most one restart per iteration (no thrash)
        ready = None  # built lazily, reused across the loop (O(arrived)
        # once per step, not per admission; invalidated when the queue
        # changes underneath it — i.e. a preemption re-push)
        while True:
            if not rep.admission_room():
                return
            if self.queue.peek_ready(now) is None:
                return  # O(1) hot-path exit: nothing has arrived
            if ready is None:
                ready = self.queue.ready(now)
            req = self.policy.select(ready, now)
            if req is None:
                return
            prompt = rep.prompt_arg(req)
            if not rep.can_take(req):
                # resume-first fallback: the policy's pick is blocked —
                # possibly *by* a victim's standing reservation. Resuming
                # an admissible swapped request never takes what the pick
                # waits for (its blocks are pre-reserved; it only needs a
                # free slot) and retiring it is the fastest way to free
                # real capacity — and it keeps EDF's tight-deadline picks
                # from starving victims behind an admission deadlock.
                swapped = next(
                    (r for r in ready if r is not req
                     and rep.pool.has_swapped(r.rid) and rep.can_take(r)),
                    None)
                if swapped is not None:
                    self.queue.remove(swapped)
                    ready.remove(swapped)
                    rep.admit(swapped, now)
                    continue
                victim = None if preempted else \
                    self.policy.victim(rep.running(), req, now)
                if victim is None:
                    return  # backend exhaustion -> queue backpressure
                vslot = rep.slot_of(victim)
                if vslot is None or rep.lane_open(vslot):
                    # a policy may hand back a stale verdict (the victim
                    # retired this iteration) or — buggy — a mid-prefill
                    # request whose open lane would keep writing into a
                    # freed slot; both are "no victim": backpressure
                    return
                if not rep.pool.preempt_frees(vslot, req.eff_gen_len,
                                              prompt=prompt):
                    # eviction could not make room — don't cost the victim
                    # its progress for nothing (and don't re-try a doomed
                    # candidate against every runner, one per step)
                    return
                self.queue.push(rep.preempt(victim, vslot, now))
                preempted = True
                ready = None  # the victim re-joined the arrived set
                if not rep.can_take(req):
                    # preempt_frees promised room for a fresh admission;
                    # a swap-resume's can_resume gate may still disagree
                    return
            self.queue.remove(req)
            if ready is not None:
                ready.remove(req)
            rep.admit(req, now)

    # -- reporting ----------------------------------------------------------------
    def snapshot(self) -> Dict[str, float]:
        now = self.clock.now()
        return self.replica.snapshot(queue_depth=self.queue.depth(now))

    def results(self) -> Dict[int, List[int]]:
        """rid -> generated tokens, for every completed request."""
        return {r.rid: list(r.tokens) for r in self.completed}


def validate_requests(requests: Sequence[Request], prompt_len: int,
                      max_gen: int, *, allow_shorter: bool = False) -> None:
    """Shared submit-time validation (ServingEngine and the router both
    gate here, before anything reaches a replica). Chunk-prefill backends
    stream prompts through lane rows at the request's own length, so they
    accept any prompt up to the engine's prompt_len budget
    (allow_shorter=True); classic batch-1 prefill jits one shape and
    keeps the exact-length contract."""
    for r in requests:
        n = len(r.prompt)
        if (n != prompt_len if not allow_shorter
                else not 0 < n <= prompt_len):
            raise ValueError(
                f"request {r.rid}: prompt length {n} "
                + (f"not in (0, {prompt_len}]" if allow_shorter
                   else f"!= engine prompt_len {prompt_len} (pad the trace)"))
        if r.eff_gen_len > max_gen:
            raise ValueError(
                f"request {r.rid}: gen_len {r.eff_gen_len} > "
                f"engine max_gen {max_gen}")


def run_to_completion(engine, requests: Sequence[Request] = (), *,
                      dt: float = 0.05, max_steps: int = 100_000,
                      on_step: Optional[Callable[[int, Dict[str, float]],
                                                 None]] = None
                      ) -> Dict[int, List[int]]:
    """Standalone drain loop (no cluster): step the engine (a ServingEngine
    or a router.ReplicaSet), advance the clock by `dt` between iterations.
    VirtualCluster.serve() is the cluster-integrated version of this
    loop."""
    engine.submit(requests)
    steps = 0
    while not engine.drained() and steps < max_steps:
        snap = engine.step()
        engine.clock.sleep(dt)
        if on_step is not None:
            on_step(steps, snap)
        steps += 1
    if not engine.drained():
        raise RuntimeError(f"serve did not drain in {max_steps} steps")
    return engine.results()
