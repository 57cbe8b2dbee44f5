"""KVBackend — the one KV-cache contract the serving plane talks to.

PR-2 left the engine, the CLI, and the metrics path branching on
`kv == "slot" | "paged"` in a dozen places; every planned feature (prefix
sharing, swap-out preemption, multi-replica pools) would have multiplied
those branches. v2 collapses them behind this protocol: a backend owns its
device cache pytree, its bookkeeping, *and its fused decode step* —
`ServingEngine` schedules rows and never learns what a block table is.

Lifecycle of one request through a backend:

    can_admit(gen_len, prompt=…)  reservation check (admission-time
                            backpressure; prompt makes it prefix-aware)
    admit(rid, gen_len, prompt=…) bind a slot + reserve worst-case
                            capacity; a prompt whose prefix the backend
                            already caches admits with shared blocks
    cached_prefix_len(slot) prompt positions admit() served from its
                            prefix cache — the engine starts prefill
                            lanes there (0 on cache-less backends)
    insert(slot, …)         classic path: scatter a batch-1 prefill cache
      — or —
    ensure(slot, pos)       chunked path: grow capacity to cover position
                            (first write into a shared block = copy-on-write)
    finish_prefill(slot)    chunked path: the slot joins the decode batch
                            (paged: registers full prompt blocks for reuse)
    decode(params, …)       one fused step over the whole row set
                            (kernel_grid: how much of its grid is live)
    advance(slot)           host bookkeeping per emitted token
    finished(slot)          declared gen budget consumed?
    evict(slot)             return capacity (double-free is an error;
                            refcounted backends drop one reference)

`metrics()` returns the backend-specific load signals to merge into the
engine snapshot (e.g. kv_block_occupancy) — the metrics path stops caring
which cache kind produced them, and `describe()` is the one-line banner
the CLI prints. SlotPool (serve/slots.py) and BlockManager
(serve/blocks.py) are the two implementations; make_kv_backend is the
only place a cache-kind string is interpreted.
"""
from __future__ import annotations

from typing import (Any, Callable, Dict, Hashable, List, Optional, Protocol,
                    Tuple, runtime_checkable)

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.env import Env

Pytree = Any

# Backend step/insert/prefill functions are pure closures over (cfg, plan,
# mesh): two replicas built from the same config share compilations. Keyed
# on the frozen config dataclasses themselves, so a distinct config can
# never collide; a non-hashable key (exotic mesh) falls back to a private
# jit. Donation is per-call, so sharing the callable is safe.
_JIT_CACHE: Dict[Tuple, Any] = {}


def shared_jit(key: Tuple[Hashable, ...], builder: Callable[[], Callable],
               **jit_kw):
    """jax.jit(builder()) memoized on `key` — the multi-replica data plane
    builds N backends per fleet, and without this each replica re-traces
    identical step functions."""
    try:
        hash(key)
    except TypeError:  # pragma: no cover - unhashable config/mesh
        return jax.jit(builder(), **jit_kw)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(builder(), **jit_kw)
        _JIT_CACHE[key] = fn
    return fn


@runtime_checkable
class KVBackend(Protocol):
    kind: str                 # registry name ("slot", "paged", ...)
    num_slots: int
    caches: Pytree            # the device cache pytree the backend owns
    chunk_prefill_ok: bool    # can prompts stream through decode lane rows?

    # -- admission / reservation -------------------------------------------
    def can_admit(self, gen_len: int, *, prompt=None) -> bool: ...
    def preempt_frees(self, slot: int, gen_len: int, *,
                      prompt=None) -> bool:
        """Would evicting `slot` make can_admit(gen_len, prompt=...) true?
        The engine asks before acting on a preemption verdict — an
        eviction that cannot make room would cost the victim its progress
        for nothing."""
        ...
    def admit(self, rid: int, gen_len: int, *, prefilling: bool = False,
              prompt=None) -> int: ...
    def cached_prefix_len(self, slot: int) -> int:
        """Prompt positions admit() served from a prefix cache (0 when the
        backend has none) — the engine's lanes start at this position."""
        ...
    def probe_prefix(self, prompt) -> int:
        """Prompt positions an admission *would* serve from this backend's
        prefix cache right now (0 on cache-less backends). Read-only — the
        router's prefix-affine policy probes every replica with it before
        choosing one."""
        ...
    def release(self) -> None:
        """Retire the backend (replica scale-down): verify the free-list
        accounting returns to empty — every block/slot back, no dangling
        reservations; leaks raise — then drop the device cache pytree."""
        ...
    def insert(self, slot: int, rid: int, prefill_caches: Pytree,
               gen_len: int) -> None: ...
    def ensure(self, slot: int, pos: int) -> None: ...
    def finish_prefill(self, slot: int) -> Any: ...
    def truncate(self, slot: int, n: int) -> None:
        """Roll the slot's committed KV back to its first `n` positions —
        the speculative-rejection path. Capacity committed past position
        n-1 returns to the pool (paged: whole blocks freed back to the
        free list, reservation re-credited); reservation-style backends
        (SlotPool) need no device work — junk past the write cursor is
        never attended and is overwritten sequentially. `n` is never below
        the prompt length (verify rows only ever extend generated
        positions), so shared prefix blocks are never in range."""
        ...

    # -- the fused step ----------------------------------------------------
    def decode(self, params: Pytree, prev_tok, meta_i: np.ndarray,
               meta_f: np.ndarray, row_slots: np.ndarray, *,
               sample: bool):
        """Run one fused decode step over T rows. meta_i/meta_f are the
        packed [META_I_ROWS,T] / [META_F_ROWS,T] arrays (launch/steps.py);
        row_slots[t] names the slot whose KV row t addresses (-1: masked).
        Returns the [T] int32 device token vector; the backend swaps its
        own (donated) cache pytree."""
        ...
    def kernel_grid(self, rows: int,
                    cur_lens: np.ndarray) -> Optional[Tuple[int, int]]:
        """The attention kernel's grid for a step of `rows` rows whose
        real rows sit at depths `cur_lens`: (programs per KV head, those
        that compute a real row's live table block). A padding row's
        programs count in the first and never in the second, though the
        kernel still computes its block 0. None on a backend whose kernel
        has no block grid."""
        ...

    # -- per-token bookkeeping / retirement --------------------------------
    def advance(self, slot: int) -> Any: ...
    def finished(self, slot: int) -> bool: ...
    def evict(self, slot: int, *, zero: bool = False) -> None: ...

    # -- host swap tier (optional; no-ops on backends without one) ---------
    def swap_out(self, slot: int) -> bool:
        """Copy the slot's live KV to a host pool and evict it. False means
        the backend cannot swap (no pool / budget full / mid-prefill) and
        the caller should restart-preempt instead."""
        ...
    def has_swapped(self, rid: int) -> bool: ...
    def can_resume(self, rid: int) -> bool: ...
    def plan_resume(self, rid: int) -> bool:
        """Take (or confirm) a standing reservation for `rid`'s swap-in
        footprint so fresh admissions queue behind the victim instead of
        starving it. Idempotent; at most one backend fleet-wide holds the
        plan; swap_in consumes it. False on backends without a swap
        tier."""
        ...
    def cancel_resume_plans(self) -> None:
        """Release every standing resume reservation (drain/release: the
        swapped records stay in the shared pool for a live peer)."""
        ...
    def swap_in(self, rid: int) -> int:
        """Restore a swapped request into a fresh slot (inverse of
        swap_out); decoding resumes from the swap point bit-identically."""
        ...
    def drop_swapped(self, rid: int) -> None: ...

    # -- introspection ------------------------------------------------------
    def info(self, slot: int) -> Any: ...
    def rid_of(self, slot: int) -> int: ...
    def active_slots(self) -> List[int]: ...
    def occupied_slots(self) -> List[int]: ...
    @property
    def free_slot_count(self) -> int: ...
    @property
    def occupancy(self) -> float: ...
    @property
    def free_capacity(self) -> int:
        """Admission capacity still available, in the backend's own units
        (paged: unreserved blocks; slot: free slots). Absolute, not a
        fraction — the router's load key uses it to break occupancy ties
        across heterogeneous pool sizes."""
        ...
    def metrics(self) -> Dict[str, float]: ...
    def describe(self) -> str: ...


def make_kv_backend(kind: str, cfg: ModelConfig, env: Env, *, num_slots: int,
                    prompt_len: int, max_gen: int, block_size: int = 16,
                    kv_blocks: Optional[int] = None,
                    prefix_cache: bool = True,
                    max_shared_fraction: float = 1.0,
                    swap: bool = False,
                    swap_budget_blocks: Optional[int] = None,
                    swap_pool=None) -> KVBackend:
    """The one cache-kind dispatch in the serving plane.

    swap=True attaches a host swap tier (serve/blocks.py HostSwapPool):
    pass a prebuilt `swap_pool` to share one across a fleet's replicas
    (ReplicaSet does), else a private pool is created with
    `swap_budget_blocks` capacity (None = unbounded)."""
    from repro.serve.blocks import (BlockManager, HostSwapPool,
                                    QuantBlockManager)
    from repro.serve.slots import SlotPool

    if swap and swap_pool is None:
        swap_pool = HostSwapPool(swap_budget_blocks)
    elif not swap:
        swap_pool = None
    if kind in ("paged", "quant"):
        cls = QuantBlockManager if kind == "quant" else BlockManager
        return cls(cfg, env, num_slots=num_slots,
                   prompt_len=prompt_len, max_gen=max_gen,
                   block_size=block_size, num_blocks=kv_blocks,
                   prefix_cache=prefix_cache,
                   max_shared_fraction=max_shared_fraction,
                   swap_pool=swap_pool)
    if kind == "slot":
        return SlotPool(cfg, env, num_slots=num_slots, prompt_len=prompt_len,
                        max_gen=max_gen)
    raise ValueError(f"unknown KV backend {kind!r} "
                     "(expected 'paged', 'quant', or 'slot')")
