"""SlotPool — a fixed-capacity, slot-addressed KV-cache backend.

The pool owns one init_cache() pytree whose batch dim is the slot dim, plus
host-side bookkeeping (which request occupies which slot, each slot's write
position). Inserting a prefilled request and stepping the mixed decode batch
are both jitted once at pool shape — admission never re-compiles, which is
what lets new requests join a running decode batch (continuous batching).

This is the `kind="slot"` KVBackend (serve/kv.py): worst-case
prompt_len+max_gen reservation per slot, kept as the measured baseline for
the paged BlockManager and as a fallback. It cannot stream prompts through
decode lane rows (chunk_prefill_ok=False — a contiguous cache has no
per-row tables to alias a chunk onto), so admission is always classic
batch-1 prefill + insert.

All device work is functional: insert/evict/decode return nothing but swap
the pool's cache pytree; the engine owns the only reference (buffers are
donated through the jitted ops, so a pool slot update does not copy the
pool).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch import steps as St
from repro.models import model as Mo
from repro.models.env import Env
from repro.serve.kv import shared_jit

Pytree = Any

FREE = -1  # slot_rid value for an unoccupied slot


@dataclass
class SlotInfo:
    rid: int
    cur_len: int  # next decode write position for this slot
    tokens_done: int  # generated so far (prefill emits the first)
    gen_len: int


class SlotPool:
    kind = "slot"
    chunk_prefill_ok = False

    def __init__(self, cfg: ModelConfig, env: Env, *, num_slots: int,
                 prompt_len: int, max_gen: int):
        if cfg.family == "vlm" or cfg.is_encdec:
            raise ValueError(
                f"{cfg.name}: continuous batching supports decoder-only "
                "archs (vlm/enc-dec prefill carries extra modalities)")
        if "local" in cfg.block_pattern + cfg.pattern_tail:
            # sliding-window blocks keep a ring-aligned cache of size
            # min(window, seq); growing a prompt-sized ring to the pool's
            # ring size would scramble the slot=pos%w alignment
            raise ValueError(
                f"{cfg.name}: sliding-window ('local') blocks are not yet "
                "supported by the slot pool (ring-buffer caches cannot be "
                "grown after prefill)")
        self.cfg = cfg
        self.env = env
        self.num_slots = num_slots
        self.prompt_len = prompt_len
        self.max_gen = max_gen
        self.caches: Pytree = Mo.init_cache(cfg, env, num_slots,
                                            prompt_len + max_gen)
        self._slots: List[Optional[SlotInfo]] = [None] * num_slots
        self._free: Deque[int] = deque(range(num_slots))  # O(1) admission
        # grow the batch-1 prefill cache to pool seq length, then write it
        # into the slot — one jitted op, slot index traced (no re-jit per slot)
        self._insert = shared_jit(
            ("slot_insert", cfg, max_gen),
            lambda: (lambda pool, c, slot: Mo.cache_insert_slot(
                pool, Mo.grow_caches(c, max_gen), slot)),
            donate_argnums=(0,))
        self._evict = shared_jit(("slot_evict", cfg),
                                 lambda: Mo.cache_evict_slot,
                                 donate_argnums=(0,))
        # two fused-step variants: an all-greedy batch runs the pure-argmax
        # step (no mask/Gumbel work); any sampling row selects the sampler
        self._decode = {
            s: shared_jit(
                ("slot_decode", cfg, env.plan, env.mesh, prompt_len, s),
                lambda s=s: St.make_fused_decode_step(cfg, env,
                                                      prompt_len=prompt_len,
                                                      sample=s),
                donate_argnums=(1,))
            for s in (False, True)}
        # row-indirected variant (T != num_slots rows, row_slots maps rows
        # onto cache slots): what speculative verify rows ride
        self._decode_spec = {
            s: shared_jit(
                ("slot_decode_spec", cfg, env.plan, env.mesh, prompt_len, s),
                lambda s=s: St.make_spec_decode_step(cfg, env,
                                                     prompt_len=prompt_len,
                                                     sample=s),
                donate_argnums=(1,))
            for s in (False, True)}

    # -- occupancy ---------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    @property
    def free_slot_count(self) -> int:
        return len(self._free)

    def acquire_slot(self) -> int:
        """Pop a free slot in O(1) (the admission loop used to rescan
        free_slots() per admitted request — O(n^2) under bursts)."""
        return self._free.popleft()

    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    def occupied_slots(self) -> List[int]:
        return self.active_slots()

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self.free_slots()) / max(self.num_slots, 1)

    @property
    def free_capacity(self) -> int:
        """Absolute admission headroom: free slots (each slot is a full
        worst-case reservation here)."""
        return len(self._free)

    def info(self, slot: int) -> Optional[SlotInfo]:
        return self._slots[slot]

    def rid_of(self, slot: int) -> int:
        s = self._slots[slot]
        return FREE if s is None else s.rid

    # -- admission / retirement --------------------------------------------
    def can_admit(self, gen_len: int, *, prompt=None) -> bool:
        return bool(self._free)  # no prefix cache: prompt can't help

    def preempt_frees(self, slot: int, gen_len: int, *,
                      prompt=None) -> bool:
        """A slot is worst-case reserved, so evicting any slot admits any
        request the engine already validated against max_gen."""
        return True

    def admit(self, rid: int, gen_len: int, *, prefilling: bool = False,
              prompt=None) -> int:
        """Bind a free slot for `rid`. The slot stays empty (info=None)
        until insert() writes the prefilled cache — the slot pool has no
        chunked-prefill path, so `prefilling` must be False. A contiguous
        per-slot cache has nothing to share, so `prompt` is ignored."""
        assert not prefilling, "slot pool has no chunked-prefill lanes"
        return self.acquire_slot()

    def cached_prefix_len(self, slot: int) -> int:
        """No prefix cache: every prompt position prefills."""
        return 0

    def probe_prefix(self, prompt) -> int:
        """No prefix cache: a router probe can never hit here."""
        return 0

    def release(self) -> None:
        """Retire the pool (replica scale-down): every slot must be back
        on the free list — a leak raises — then the cache is dropped."""
        live = [i for i, s in enumerate(self._slots) if s is not None]
        if live:
            raise RuntimeError(f"release with occupied slots {live}")
        if len(self._free) != self.num_slots:
            raise RuntimeError(
                f"release leaked {self.num_slots - len(self._free)} slots "
                "(acquired but never evicted)")
        self.caches = None

    def insert(self, slot: int, rid: int, prefill_caches: Pytree,
               gen_len: int) -> None:
        """Bind `rid` to `slot` and write its prefilled (batch-1, length
        prompt_len) cache into the pool."""
        assert self._slots[slot] is None, f"slot {slot} occupied"
        if slot in self._free:  # direct pool use (tests): claim this slot
            self._free.remove(slot)
        self.caches = self._insert(self.caches, prefill_caches,
                                   jnp.asarray(slot, jnp.int32))
        self._slots[slot] = SlotInfo(rid=rid, cur_len=self.prompt_len,
                                     tokens_done=1, gen_len=gen_len)

    def ensure(self, slot: int, pos: int) -> None:
        """Capacity is reserved wholesale at admission — nothing to grow."""

    def truncate(self, slot: int, n: int) -> None:
        """Speculative rollback is free on a reserved contiguous cache:
        positions past the write cursor are never attended (attention
        depth is cur_len) and the sequential decode overwrites them before
        the cursor ever reaches them."""

    def finish_prefill(self, slot: int) -> SlotInfo:
        raise NotImplementedError("slot pool has no chunked-prefill lanes")

    def evict(self, slot: int, *, zero: bool = False) -> None:
        """Free `slot`. Insert fully overwrites a slot, so zeroing is only
        for hygiene (tests assert evicted slots hold no stale KV)."""
        if self._slots[slot] is not None:
            self._free.append(slot)
        self._slots[slot] = None
        if zero:
            self.caches = self._evict(self.caches,
                                      jnp.asarray(slot, jnp.int32))

    # -- host swap tier: not supported (worst-case reservation has no
    # partial progress worth preserving at block granularity); the engine's
    # swap path falls back to restart preemption on False ---------------------
    def swap_out(self, slot: int) -> bool:
        return False

    def has_swapped(self, rid: int) -> bool:
        return False

    def can_resume(self, rid: int) -> bool:
        return False

    def plan_resume(self, rid: int) -> bool:
        return False

    def cancel_resume_plans(self) -> None:
        pass

    def swap_in(self, rid: int) -> int:
        raise NotImplementedError("slot pool has no host swap tier")

    def drop_swapped(self, rid: int) -> None:
        pass

    # -- the fused step -------------------------------------------------------
    def decode(self, params, prev_tok, meta_i, meta_f, row_slots, *,
               sample: bool):
        """One fused step over the contiguous pool. The classic shape
        (T == num_slots rows) addresses slots directly (row == slot) and
        ignores row_slots; a wider batch — speculative verify rows stacked
        past the slots — runs the row-indirected step, where row_slots
        maps each row onto its slot's cache row (-1 masks)."""
        if meta_i.shape[1] != self.num_slots:
            nxt, self.caches = self._decode_spec[sample](
                params, self.caches, prev_tok, jnp.asarray(meta_i),
                jnp.asarray(meta_f), jnp.asarray(row_slots))
            return nxt
        del row_slots
        nxt, self.caches = self._decode[sample](
            params, self.caches, prev_tok, jnp.asarray(meta_i),
            jnp.asarray(meta_f))
        return nxt

    def kernel_grid(self, rows: int,
                    cur_lens: np.ndarray) -> Optional[Tuple[int, int]]:
        return None  # contiguous rows: the kernel has no block grid

    # -- decode-batch views ---------------------------------------------------
    def advance(self, slot: int) -> SlotInfo:
        """Record one decoded token for `slot`; returns the updated info."""
        s = self._slots[slot]
        assert s is not None
        s.cur_len += 1
        s.tokens_done += 1
        return s

    def finished(self, slot: int) -> bool:
        s = self._slots[slot]
        return s is not None and s.tokens_done >= s.gen_len

    # -- reporting ----------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        return {}

    def describe(self) -> str:
        return (f"slot KV: {self.num_slots} slots x "
                f"{self.prompt_len + self.max_gen} reserved tokens")

    # -- introspection (tests) ----------------------------------------------
    def read_slot(self, slot: int) -> Pytree:
        return Mo.cache_read_slot(self.caches, jnp.asarray(slot, jnp.int32))
