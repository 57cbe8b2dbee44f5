"""BlockManager — a paged KV cache: global block pool + per-request tables.

The slot pool (serve/slots.py) reserves prompt_len + max_gen KV per slot for
a request's whole lifetime, so one long-tail gen length pins worst-case
memory for everyone. The BlockManager instead owns a global pool of
fixed-size KV blocks (Mo.init_paged_cache) and a host-side [num_slots, MB]
block table per request; blocks are allocated on demand as a request's
cur_len crosses block boundaries and returned to an O(1) free list at
retirement, so resident KV tracks what requests actually wrote — at a fixed
HBM budget the pool admits 2-4x the concurrent requests of slot reservation.

Admission is gated by *reservation*: a request reserves (but does not yet
allocate) the blocks its declared gen_len can ever need, so on-demand
allocation can never deadlock mid-decode and block exhaustion surfaces as
clean queue backpressure at admit time.

Physical block 0 is the null block: never allocated, it absorbs the writes
of masked rows (free slots / idle prefill lanes) in the fused decode step.

Sliding-window ('local') layers get their own window-sized tables: a ring
of ceil(w/bs) blocks written at pos % w — softmax over keys is permutation-
invariant and RoPE is applied at write time, so the ring never needs
unscrambling (this is what lets recurrentgemma-style archs serve here while
the slot pool still rejects them).

Reclaim ordering is hit-count-weighted: when the free list runs dry, the
retained (refcount-0, still-registered) block with the fewest lifetime
prefix-cache hits is unregistered first, LRU insertion order breaking
ties — a hot system prompt outlives a parade of one-off templates. A
`max_shared_fraction` residency cap bounds how much of the pool the
prefix index may retain at all, so one tenant's template churn cannot
monopolize a replica's pool (blocks past the cap simply never register;
they free normally at retirement).

Prefix caching (copy-on-write sharing): real multi-user traffic is
dominated by shared prompt prefixes (system prompts, few-shot templates).
Full prompt blocks are content-addressed by a per-block hash *chain*
(h_j = H(h_{j-1} || tokens_j), vLLM-style, so a block hash commits to its
whole prefix); a request whose prompt chain hits the index admits with its
table pointing at the shared physical blocks — those prefill positions are
never recomputed (the engine starts its lanes at `cached_len`). Sharing is
refcounted: a block returns to the pool only when its last reference drops,
and registered blocks whose refcount hits zero are *retained* in an LRU
reclaim list (still KV-valid, still admission capacity) until the free list
runs dry. The first write into a still-shared block — only ever the
boundary block of a fully-cached prompt — triggers copy-on-write into a
fresh block (Mo.make_paged_copy), so two requests sharing a prefix can
never observe each other's writes. The double-free guard extends to
refcounts: freeing through a table whose entry is already at refcount zero
raises before the free list is poisoned.
"""
from __future__ import annotations

import hashlib
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_decode.kernel import grid_programs
from repro.launch import steps as St
from repro.models import model as Mo
from repro.models.env import Env
from repro.serve.kv import shared_jit

Pytree = Any

FREE = -1

RECURRENT_KINDS = ("rglru", "rwkv")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclass
class PagedSlot:
    rid: int
    cur_len: int  # next decode write position
    tokens_done: int
    gen_len: int
    plen: int = 0  # the request's own prompt length (0: engine prompt_len)
    prefilling: bool = False  # still consuming prompt chunks (lane rows)
    alloc_g: int = 0  # global-table entries bound so far (shared + private)
    alloc_l: int = 0  # local-table blocks allocated so far
    reserved: int = 0  # blocks reserved but not yet allocated
    cached_len: int = 0  # prompt tokens served from the prefix cache
    shared_g: int = 0  # leading table entries referencing shared blocks
    hashes: Tuple[bytes, ...] = ()  # prompt block hash chain (full blocks)


@dataclass
class SwapRecord:
    """One swapped-out request: its block KV pulled to host numpy plus the
    PagedSlot bookkeeping needed to rebuild the slot on swap-in."""
    rid: int
    payload: Any  # numpy pytree congruent to the device pool, block dim = n
    n_blocks: int
    nbytes: int
    cur_len: int
    tokens_done: int
    gen_len: int
    reserved: int
    cached_len: int
    alloc_g: int
    alloc_l: int
    plen: int = 0


class HostSwapPool:
    """Host-side (numpy) KV block pool — the swap tier under the device
    pool. Preemption copies a victim's blocks out instead of discarding
    them; re-admission scatters them back and decoding resumes from the
    swap point bit-identically (no recompute). One pool is shared across a
    ReplicaSet's replicas (host RAM is node-local), so a request drained
    off one replica can restore on another.

    Accounting mirrors the device pool's: `budget_blocks` caps host
    residency (a full budget makes swap_out fall back to restart
    preemption), and the last backend to detach leak-checks that every
    swapped request was either restored or dropped."""

    def __init__(self, budget_blocks: Optional[int] = None):
        if budget_blocks is not None and budget_blocks < 0:
            raise ValueError(f"budget_blocks must be >= 0 or None, got "
                             f"{budget_blocks}")
        self.budget_blocks = budget_blocks  # None = unbounded
        self._records: Dict[int, SwapRecord] = {}
        self._attached = 0
        # resume-plan ownership: at most one backend fleet-wide holds a
        # standing re-admission reservation for a swapped rid (two
        # replicas both reserving the same victim's footprint would
        # double-commit fleet capacity for one resume)
        self._planned: Dict[int, Any] = {}

    @property
    def blocks_resident(self) -> int:
        return sum(r.n_blocks for r in self._records.values())

    @property
    def bytes_resident(self) -> int:
        return sum(r.nbytes for r in self._records.values())

    def can_store(self, n_blocks: int) -> bool:
        return (self.budget_blocks is None
                or self.blocks_resident + n_blocks <= self.budget_blocks)

    def store(self, rec: SwapRecord) -> None:
        assert rec.rid not in self._records, \
            f"rid {rec.rid} is already swapped out"
        assert self.can_store(rec.n_blocks), "host swap budget exhausted"
        self._records[rec.rid] = rec

    def has(self, rid: int) -> bool:
        return rid in self._records

    def peek(self, rid: int) -> SwapRecord:
        return self._records[rid]

    def take(self, rid: int) -> SwapRecord:
        """Remove and return `rid`'s record (swap-in frees host residency)."""
        self._planned.pop(rid, None)
        return self._records.pop(rid)

    def drop(self, rid: int) -> None:
        """Discard a swapped request (cancelled / restarted): its host
        blocks free without a restore."""
        self._planned.pop(rid, None)
        self._records.pop(rid, None)

    # -- resume-plan ownership ------------------------------------------------
    def plan(self, rid: int, owner: Any) -> None:
        cur = self._planned.get(rid)
        assert cur is None or cur is owner, \
            f"rid {rid} already has a resume plan on another backend"
        self._planned[rid] = owner

    def planner(self, rid: int) -> Optional[Any]:
        return self._planned.get(rid)

    def unplan(self, rid: int) -> None:
        self._planned.pop(rid, None)

    def attach(self) -> None:
        self._attached += 1

    def detach(self) -> None:
        """A backend released its device pool. When the last one detaches,
        the host pool must be empty — a swapped request nobody can ever
        restore is a leak, same class of bug as a lost device block."""
        self._attached -= 1
        if self._attached <= 0 and self._records:
            held = sorted(self._records)
            raise RuntimeError(
                f"host swap pool leaked {len(held)} swapped request(s) "
                f"{held} ({self.blocks_resident} blocks) at last detach")


class BlockManager:
    kind = "paged"
    _quant = False  # QuantBlockManager flips this: int8 pool + scales

    def __init__(self, cfg: ModelConfig, env: Env, *, num_slots: int,
                 prompt_len: int, max_gen: int, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 max_shared_fraction: float = 1.0,
                 swap_pool: Optional[HostSwapPool] = None):
        if cfg.family == "vlm" or cfg.is_encdec:
            raise ValueError(
                f"{cfg.name}: continuous batching supports decoder-only "
                "archs (vlm/enc-dec prefill carries extra modalities)")
        kinds = set(cfg.block_pattern) | set(cfg.pattern_tail)
        if not kinds <= set(Mo.PAGEABLE_KINDS) | set(RECURRENT_KINDS):
            raise ValueError(f"{cfg.name}: kinds {sorted(kinds)} have no "
                             "paged-cache layout")
        self.cfg = cfg
        self.env = env
        self.num_slots = num_slots
        self.prompt_len = prompt_len
        self.max_gen = max_gen
        self.block_size = block_size
        self.window = cfg.local_window
        self.has_global = bool(kinds & {"attn", "moe"})
        self.has_local = "local" in kinds
        # recurrent state rows pin the decode batch to slot == row
        self.has_state = bool(kinds & set(RECURRENT_KINDS))
        # recurrent state can't parallelize a prompt chunk inside one step,
        # and window-ring writes would wrap onto each other within a chunk
        # (rows p and p+w share ring slot p%w); both admit via batch-1
        # prefill + paged insert instead
        self.chunk_prefill_ok = not self.has_state and not self.has_local
        max_kv = prompt_len + max_gen  # last written pos < prompt+gen-1
        bs = block_size
        self.mb_global = _ceil_div(max_kv, bs) if self.has_global else 0
        self.mb_local = (_ceil_div(min(self.window, max_kv), bs)
                         if self.has_local else 0)
        worst = num_slots * (self.mb_global + self.mb_local)
        self.num_blocks = (num_blocks if num_blocks is not None
                           else worst + 1)  # +1: the null block
        if self.num_blocks < 1 + self.mb_global + self.mb_local:
            raise ValueError(
                f"num_blocks={self.num_blocks} cannot hold even one request "
                f"({self.mb_global}+{self.mb_local} blocks + null)")
        self.caches: Pytree = Mo.init_paged_cache(
            cfg, env, num_slots, self.num_blocks, bs, quant=self._quant)
        # -- host swap tier (tentpole b): preemption copies victim blocks
        # out instead of discarding; the pool may be shared fleet-wide
        self.swap_pool = swap_pool
        if swap_pool is not None:
            swap_pool.attach()
        self._swap_out_bytes = 0
        self._swap_in_bytes = 0
        self._swapped_blocks = 0  # cumulative blocks this backend swapped
        # swap-aware admission: rid -> blocks this backend holds reserved
        # for the rid's planned swap-in (counted in _reserved_total)
        self._resume_plans: Dict[int, int] = {}
        # host-side tables: row per slot, 0 = unallocated (null block)
        self.table = np.zeros((num_slots, max(self.mb_global, 1)), np.int32)
        self.table_local = np.zeros((num_slots, max(self.mb_local, 1)),
                                    np.int32)
        self._slots: List[Optional[PagedSlot]] = [None] * num_slots
        self._free_slots: Deque[int] = deque(range(num_slots))
        self._free_blocks: Deque[int] = deque(range(1, self.num_blocks))
        # mirror of _free_blocks for the O(1) double-free guard: a block id
        # returned twice would sit in the free list twice and get handed to
        # two requests, silently cross-writing their KV
        self._free_block_set: Set[int] = set(self._free_blocks)
        self._reserved_total = 0  # blocks promised to admitted requests
        # -- prefix cache state (sharing only applies to global tables; a
        # window ring rewrites positions in place and recurrent state is
        # not content-addressable, so those archs keep prefix_cache off)
        self.prefix_cache = (bool(prefix_cache) and self.has_global
                             and self.chunk_prefill_ok)
        if not 0.0 <= max_shared_fraction <= 1.0:
            raise ValueError(f"max_shared_fraction must be in [0, 1], got "
                             f"{max_shared_fraction}")
        self.max_shared_fraction = float(max_shared_fraction)
        self._ref = np.zeros(self.num_blocks, np.int64)  # table references
        self._cached: Dict[bytes, int] = {}   # prefix-chain hash -> block id
        self._hash_of: Dict[int, bytes] = {}  # registered block -> its hash
        self._hits: Dict[int, int] = {}  # registered block -> cache hits
        # registered blocks whose last reference dropped: still KV-valid,
        # still admission capacity. Values are insertion sequence numbers:
        # reclaim pops the fewest-hits entry, LRU breaking ties.
        self._reclaim: Dict[int, int] = {}
        self._reclaim_seq = 0
        self._hit_tokens = 0     # prompt tokens served from the cache
        self._lookup_tokens = 0  # prompt tokens probed at admission
        self._cow_copies = 0
        # one-entry probe memo: a backpressured queue head re-probes every
        # scheduler step, and one admission probes up to three times
        # (can_admit, admit's assert, admit) — don't re-hash the prompt
        # each time. Invalidated whenever the index changes.
        self._probe_memo: Optional[Tuple[bytes, tuple]] = None
        # shared_jit: N replicas built from the same config share these
        # compilations instead of re-tracing identical closures per pool
        self._insert = shared_jit(("paged_insert", cfg, bs),
                                  lambda: Mo.make_paged_insert(cfg, bs),
                                  donate_argnums=(0,))
        self._copy = shared_jit(("paged_copy", cfg),
                                lambda: Mo.make_paged_copy(cfg),
                                donate_argnums=(0,))
        self._evict = shared_jit(("paged_evict", cfg),
                                 lambda: Mo.make_paged_evict(cfg),
                                 donate_argnums=(0,))
        self._read = shared_jit(("paged_read", cfg),
                                lambda: Mo.make_paged_read(cfg))
        # two fused-step variants: an all-greedy batch runs the pure-argmax
        # step (no mask/Gumbel work); any sampling row selects the sampler
        self._decode = {
            s: shared_jit(
                ("paged_decode", cfg, env.plan, env.mesh, prompt_len, s),
                lambda s=s: St.make_paged_decode_step(cfg, env,
                                                      prompt_len=prompt_len,
                                                      sample=s),
                donate_argnums=(1,))
            for s in (False, True)}

    # -- sizing / admission math -------------------------------------------
    def blocks_for(self, gen_len: int, plen: Optional[int] = None) -> int:
        """Physical blocks a request with this gen_len can ever touch (its
        KV spans positions [0, plen + gen_len - 1)). `plen` defaults to
        the engine's prompt_len budget; chunked admissions pass the
        request's own prompt length so a short multi-turn opener doesn't
        reserve a full-length prompt's worst case."""
        kv = max((plen or self.prompt_len) + gen_len - 1, 1)
        n = _ceil_div(kv, self.block_size) if self.has_global else 0
        if self.has_local:
            n += _ceil_div(min(self.window, kv), self.block_size)
        return n

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1

    @property
    def free_unreserved(self) -> int:
        """Free + reclaimable (cache-retained, refcount 0) minus promised
        reservations — the capacity admission may still hand out."""
        return (len(self._free_blocks) + len(self._reclaim)
                - self._reserved_total)

    def _prompt_hashes(self, prompt) -> Tuple[bytes, ...]:
        """Content-hash chain over the prompt's *full* blocks. Each link
        commits to the whole prefix up to and including its block, so a
        single dict lookup per block matches vLLM's prefix trie. blake2b,
        not Python hash(): a collision here would silently serve one
        request another's KV."""
        bs = self.block_size
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32))
        h = b"paged-prefix-root"
        out = []
        for j in range(len(toks) // bs):
            m = hashlib.blake2b(h, digest_size=16)
            m.update(toks[j * bs:(j + 1) * bs].tobytes())
            h = m.digest()
            out.append(h)
        return tuple(out)

    def _probe(self, prompt) -> Tuple[Tuple[bytes, ...], int, int, int]:
        """(hashes, shared, cached_len, cow): how much of `prompt` the
        cache already holds. cached_len is capped at prompt_len - 1 — the
        last prompt token always runs (its logits emit the first generated
        token), so a fully-cached prompt keeps exactly one lane row and its
        write into the shared boundary block is the copy-on-write case
        (cow=1 reserves the fresh block that copy will need)."""
        if prompt is None or not self.prefix_cache:
            return (), 0, 0, 0
        key = np.ascontiguousarray(np.asarray(prompt, np.int32)).tobytes()
        if self._probe_memo is not None and self._probe_memo[0] == key:
            return self._probe_memo[1]
        hashes = self._prompt_hashes(prompt)
        shared = 0
        for h in hashes:
            if h not in self._cached:
                break
            shared += 1
        cached_len = min(shared * self.block_size, len(prompt) - 1)
        cow = 1 if shared * self.block_size > cached_len else 0
        self._probe_memo = (key, (hashes, shared, cached_len, cow))
        return hashes, shared, cached_len, cow

    def can_admit(self, gen_len: int, *, prompt=None) -> bool:
        """With `prompt`, admission is prefix-aware: shared blocks cost no
        fresh capacity, except that resurrecting a reclaimable block (and
        the one copy-on-write block of a fully-cached prompt) re-commits
        physical capacity the reservation math must still cover."""
        if not self._free_slots:
            return False
        hashes, shared, _, cow = self._probe(prompt)
        resurrect = sum(1 for h in hashes[:shared]
                        if self._ref[self._cached[h]] == 0)
        plen = len(prompt) if prompt is not None else None
        need = self.blocks_for(gen_len, plen) - shared + cow + resurrect
        return need <= self.free_unreserved

    def preempt_frees(self, slot: int, gen_len: int, *,
                      prompt=None) -> bool:
        """Evicting `slot` frees its unspent reservation plus every block
        it holds the *last* reference to (shared blocks merely decref —
        registered ones land in the reclaim list, which still counts as
        capacity) — admit iff that covers the candidate's need. With
        `prompt`, the need is prefix-discounted exactly like can_admit's,
        so a hot-prefix candidate is not stalled behind worst-case math;
        hit blocks whose only holder is the victim count as resurrections
        (the eviction parks them in reclaim, the candidate pulls them
        right back out)."""
        s = self._slots[slot]
        assert s is not None
        vblocks = {int(self.table[slot, j]) for j in range(s.alloc_g)}
        vblocks |= {int(self.table_local[slot, j])
                    for j in range(s.alloc_l)}
        freed = s.reserved + sum(  # replint: ignore[R001] -- order-insensitive reduction: sum over the set is the same for any iteration order
            1 for b in vblocks if self._ref[b] == 1)
        hashes, shared, _, cow = self._probe(prompt)
        resurrect = 0
        for h in hashes[:shared]:
            bid = self._cached[h]
            if self._ref[bid] == 0 or (self._ref[bid] == 1
                                       and bid in vblocks):
                resurrect += 1
        plen = len(prompt) if prompt is not None else None
        need = self.blocks_for(gen_len, plen) - shared + cow + resurrect
        return need <= self.free_unreserved + freed

    # -- occupancy ----------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def active_slots(self) -> List[int]:
        """Slots in the decode batch (prefilling slots ride lane rows)."""
        return [i for i, s in enumerate(self._slots)
                if s is not None and not s.prefilling]

    def occupied_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free_slots) / max(self.num_slots, 1)

    @property
    def free_capacity(self) -> int:
        """Absolute admission headroom: unreserved blocks (slots are
        rarely the binding constraint on a paged pool)."""
        return max(self.free_unreserved, 0)

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by live requests (cache-retained blocks with
        no referents are reclaimable capacity, not use)."""
        return (self.usable_blocks - len(self._free_blocks)
                - len(self._reclaim))

    @property
    def block_occupancy(self) -> float:
        """Fraction of the pool committed (allocated + reserved) — the
        admission-honest load signal published to the autoscaler."""
        committed = self.blocks_in_use + self._reserved_total
        return committed / max(self.usable_blocks, 1)

    def info(self, slot: int) -> Optional[PagedSlot]:
        return self._slots[slot]

    def rid_of(self, slot: int) -> int:
        s = self._slots[slot]
        return FREE if s is None else s.rid

    # -- admission / allocation --------------------------------------------
    def admit(self, rid: int, gen_len: int, *, prefilling: bool = False,
              prompt=None) -> int:
        """Reserve a slot + the request's worst-case blocks; allocation
        itself happens on demand via ensure(). Returns the slot.

        With `prompt` (chunked admissions only), the prefix cache is
        probed first: hit blocks are attached (refcounted) to the slot's
        table and `cached_len` records how many prompt positions never
        need a prefill lane — the engine starts its lanes there."""
        use_prefix = prefilling and prompt is not None
        assert self.can_admit(gen_len, prompt=prompt if use_prefix else None)
        slot = self._free_slots.popleft()
        plen = len(prompt) if prompt is not None else self.prompt_len
        need = self.blocks_for(gen_len, plen)
        hashes, shared, cached_len, cow = (
            self._probe(prompt) if use_prefix else ((), 0, 0, 0))
        s = PagedSlot(rid=rid, cur_len=0, tokens_done=0, gen_len=gen_len,
                      plen=plen, prefilling=prefilling,
                      reserved=need - shared + cow,
                      cached_len=cached_len, shared_g=shared, hashes=hashes)
        self._slots[slot] = s
        for j in range(shared):
            self._attach(slot, j, self._cached[hashes[j]])
        s.alloc_g = shared
        self._reserved_total += s.reserved
        if use_prefix and self.prefix_cache:
            self._lookup_tokens += len(prompt)
            self._hit_tokens += cached_len
        return slot

    def _attach(self, slot: int, j: int, bid: int) -> None:
        """Point table entry j at shared block `bid` (incref; resurrect it
        from the reclaim list if its last holder already retired). Every
        attach is a cache hit — the count is what reclaim ordering
        weighs."""
        if self._ref[bid] == 0:
            del self._reclaim[bid]
        self._ref[bid] += 1
        self._hits[bid] = self._hits.get(bid, 0) + 1
        self.table[slot, j] = bid

    def _unregister_coldest(self) -> int:
        """Pop the reclaimable block with the fewest lifetime cache hits
        (LRU insertion order breaks ties — pure LRU is the zero-hit
        degenerate case) and drop its prefix-index entry."""
        bid = min(self._reclaim,
                  key=lambda b: (self._hits.get(b, 0), self._reclaim[b]))
        del self._reclaim[bid]
        del self._cached[self._hash_of.pop(bid)]
        self._hits.pop(bid, None)
        self._probe_memo = None  # the index shrank; memoized hits may lie
        return bid

    def _take_block(self) -> int:
        """A fresh physical block: the free list first, else reclaim the
        coldest cache-retained block (hit-count-weighted, LRU ties)."""
        if self._free_blocks:
            bid = self._free_blocks.popleft()
            self._free_block_set.discard(bid)
            return bid
        return self._unregister_coldest()

    def _release(self, bid: int) -> bool:
        """Drop one reference to `bid`; returns True iff the block went
        back to the free list (registered blocks are retained, reclaimable
        coldest-first, so a later identical prompt still hits)."""
        self._ref[bid] -= 1
        if self._ref[bid] > 0:
            return False
        if bid in self._hash_of:
            self._reclaim[bid] = self._reclaim_seq
            self._reclaim_seq += 1
            return False
        self._free_blocks.append(bid)
        self._free_block_set.add(bid)
        return True

    def _alloc(self, slot: int, local: bool) -> None:
        s = self._slots[slot]
        bid = self._take_block()
        self._ref[bid] = 1
        tbl = self.table_local if local else self.table
        if local:
            tbl[slot, s.alloc_l] = bid
            s.alloc_l += 1
        else:
            tbl[slot, s.alloc_g] = bid
            s.alloc_g += 1
        s.reserved -= 1
        self._reserved_total -= 1
        assert s.reserved >= 0, "request outgrew its reservation"

    def _cow(self, slot: int, j: int) -> None:
        """Copy-on-write: `slot` is about to write into shared table entry
        j (the boundary block of a fully-cached prompt). Copy the block's
        KV into a fresh block (reserved at admission), repoint the table,
        drop the shared reference — the sharer(s) and the cache keep the
        original; this request's writes land in its private copy."""
        s = self._slots[slot]
        old = int(self.table[slot, j])
        new = self._take_block()
        self._ref[new] = 1
        self.caches = self._copy(self.caches, jnp.asarray(old, jnp.int32),
                                 jnp.asarray(new, jnp.int32))
        self.table[slot, j] = new
        s.shared_g = j
        s.reserved -= 1
        self._reserved_total -= 1
        assert s.reserved >= 0, "copy-on-write outgrew its reservation"
        self._cow_copies += 1
        self._release(old)

    def ensure(self, slot: int, pos: int) -> None:
        """Allocate blocks so `slot` can write KV at logical position `pos`
        (and the matching window-ring position). On-demand growth: called
        right before every decode/prefill-chunk step. Writes are strictly
        sequential from cached_len, so the only write that can land in a
        shared block is the first one past a partially-cached boundary —
        that is the copy-on-write trigger."""
        s = self._slots[slot]
        assert s is not None
        bs = self.block_size
        if self.has_global:
            if s.shared_g * bs > s.cached_len and pos >= s.cached_len:
                self._cow(slot, s.shared_g - 1)
            while s.alloc_g < pos // bs + 1:
                self._alloc(slot, local=False)
        if self.has_local:
            ring_hi = min(pos, self.window - 1)
            while s.alloc_l < ring_hi // bs + 1:
                self._alloc(slot, local=True)

    def truncate(self, slot: int, n: int) -> None:
        """Roll `slot`'s committed KV back to its first `n` positions —
        the speculative-rejection path. Blocks wholly past position n-1
        go back through _release (refcounted: a registered block is
        retained for reclaim, a private one returns to the free list) and
        their reservation is re-credited, so a rejected draft costs the
        pool nothing. Junk KV inside the kept boundary block needs no
        device work: attention depth is cur_len, and the sequential write
        cursor overwrites it before it could ever be attended.

        Never reaches shared prefix blocks: verify rows only extend
        generated positions, so n >= prompt_len >= shared_g * block_size
        (COW has already privatized the boundary block by the time a slot
        decodes)."""
        s = self._slots[slot]
        assert s is not None and not s.prefilling
        keep = _ceil_div(n, self.block_size)
        assert keep >= s.shared_g, \
            f"truncate({n}) would free shared prefix blocks of slot {slot}"
        for j in range(keep, s.alloc_g):
            self._release(int(self.table[slot, j]))
            self.table[slot, j] = 0
            s.reserved += 1
            self._reserved_total += 1
        s.alloc_g = min(s.alloc_g, keep)
        # local ring tables are untouched: speculative decode is gated off
        # sliding-window archs (has_local pools never see truncate)

    def _tables_of(self, slot: int):
        return (jnp.asarray(self.table[slot]),
                jnp.asarray(self.table_local[slot]))

    def insert(self, slot: int, rid: int, prefill_caches: Pytree,
               gen_len: int) -> None:
        """Classic admission (SlotPool-compatible): bind `rid` to `slot`
        (pre-acquired via admit) or acquire one, then scatter the batch-1
        prefill cache into the slot's blocks. Used for recurrent-state
        archs and as the non-chunked fallback."""
        if self._slots[slot] is None:
            # direct pool use (tests): take this specific slot
            assert self.can_admit(gen_len), "block pool exhausted"
            self._free_slots.remove(slot)
            need = self.blocks_for(gen_len)
            self._slots[slot] = PagedSlot(rid=rid, cur_len=0, tokens_done=0,
                                          gen_len=gen_len, reserved=need)
            self._reserved_total += need
        s = self._slots[slot]
        assert s.shared_g == 0, \
            "classic insert scatters the whole prompt; it cannot target a " \
            "slot admitted with shared prefix blocks"
        s.rid = rid
        s.plen = self.prompt_len  # classic prefill scatters the full shape
        self.ensure(slot, self.prompt_len - 1)
        tg, tl = self._tables_of(slot)
        self.caches = self._insert(self.caches, prefill_caches,
                                   jnp.asarray(slot, jnp.int32), tg, tl)
        s.cur_len = self.prompt_len
        s.tokens_done = 1
        s.prefilling = False

    def finish_prefill(self, slot: int) -> PagedSlot:
        """Chunked prefill consumed the whole prompt: the slot joins the
        decode batch (its first token was emitted by the last lane row).
        The slot's full prompt blocks now hold valid KV — register any not
        yet in the prefix index so later identical prefixes hit. (Full
        prompt blocks are never written again: generation writes start at
        prompt_len, past the last registered block.)"""
        s = self._slots[slot]
        assert s is not None and s.prefilling
        s.prefilling = False
        s.cur_len = s.plen or self.prompt_len
        s.tokens_done = 1
        if self.prefix_cache:
            cap = int(self.max_shared_fraction * self.usable_blocks)
            for j, h in enumerate(s.hashes):
                if h in self._cached:
                    continue
                if len(self._hash_of) >= cap:
                    # residency cap: the prefix index may not retain more
                    # than max_shared_fraction of the pool. Make room by
                    # unregistering the coldest *reclaimable* entry; if
                    # every registered block is still referenced, this
                    # block simply stays private (freed normally at
                    # retirement) — one tenant's template churn cannot
                    # monopolize the pool.
                    if not self._reclaim:
                        continue
                    freed = self._unregister_coldest()
                    self._free_blocks.append(freed)
                    self._free_block_set.add(freed)
                bid = int(self.table[slot, j])
                self._cached[h] = bid
                self._hash_of[bid] = h
                self._hits.setdefault(bid, 0)
                self._probe_memo = None  # the index grew; re-probe
        return s

    # -- the fused step -------------------------------------------------------
    def decode(self, params, prev_tok, meta_i, meta_f, row_slots, *,
               sample: bool):
        """One fused step over the block pool. row_slots[t] names the slot
        whose tables row t addresses (decode rows: the slot itself; prefill
        lane rows: the admitting slot; -1: masked row -> null tables)."""
        rs = np.asarray(row_slots)
        safe = np.clip(rs, 0, self.num_slots - 1)
        live = (rs >= 0)[:, None]
        tables = {"global": jnp.asarray(np.where(live, self.table[safe], 0))}
        if self.has_local:
            tables["local"] = jnp.asarray(
                np.where(live, self.table_local[safe], 0))
        nxt, self.caches = self._decode[sample](
            params, self.caches, prev_tok, jnp.asarray(meta_i),
            jnp.asarray(meta_f), tables)
        return nxt

    def kernel_grid(self, rows: int,
                    cur_lens: np.ndarray) -> Optional[Tuple[int, int]]:
        """The paged kernel's chunk loop over the global tables decode()
        builds (kernels/paged_decode/kernel.py grid_programs)."""
        if not self.has_global:
            return None
        return grid_programs(rows, cur_lens, self.block_size,
                             self.table.shape[1])

    # -- decode-batch views -------------------------------------------------
    def advance(self, slot: int) -> PagedSlot:
        s = self._slots[slot]
        assert s is not None and not s.prefilling
        s.cur_len += 1
        s.tokens_done += 1
        return s

    def finished(self, slot: int) -> bool:
        s = self._slots[slot]
        return (s is not None and not s.prefilling
                and s.tokens_done >= s.gen_len)

    # -- retirement ---------------------------------------------------------
    def evict(self, slot: int, *, zero: bool = False) -> None:
        """Free `slot`: drop one reference per table entry — a block
        returns to the free list only when its last reference drops AND it
        is not cache-registered (registered blocks are retained in the
        reclaim list so later identical prefixes still hit). Zeroing is
        hygiene only (tests) and skips blocks that stay shared or cached.

        Double frees are hard errors, not silent corruption: evicting an
        already-free slot raises, and a table entry whose block is already
        at refcount zero or sitting in the free list (an aliased table —
        exactly the corruption refcounting must never introduce) raises
        before the free list is poisoned."""
        s = self._slots[slot]
        if s is None:
            raise RuntimeError(
                f"double free: slot {slot} is already free (its block "
                "table was returned to the pool once)")
        freeing_g = [int(self.table[slot, j]) for j in range(s.alloc_g)]
        freeing_l = [int(self.table_local[slot, j])
                     for j in range(s.alloc_l)]
        freeing = freeing_g + freeing_l
        dup = [b for b in freeing
               if b in self._free_block_set or self._ref[b] <= 0]
        if len(set(freeing)) != len(freeing):  # within-table alias
            dup += [b for b in sorted(set(freeing)) if freeing.count(b) > 1]
        if dup:
            raise RuntimeError(
                f"double free: slot {slot} block table names free block(s) "
                f"{sorted(set(dup))} — the free list would hand them to "
                "two requests")
        if zero:
            # only blocks this eviction actually returns to the free list
            # may be zeroed; shared or cache-retained blocks keep their KV
            # (padding 0s land in the null block, which absorbs anything)
            dropping = {b for b in freeing
                        if self._ref[b] == 1 and b not in self._hash_of}
            zg = np.zeros_like(self.table[slot])
            gl = [b for b in freeing_g if b in dropping]
            zg[:len(gl)] = gl
            zl = np.zeros_like(self.table_local[slot])
            ll = [b for b in freeing_l if b in dropping]
            zl[:len(ll)] = ll
            self.caches = self._evict(self.caches,
                                      jnp.asarray(slot, jnp.int32),
                                      jnp.asarray(zg), jnp.asarray(zl))
        for b in freeing:
            self._release(b)
        self.table[slot, :] = 0
        self.table_local[slot, :] = 0
        self._reserved_total -= s.reserved
        self._slots[slot] = None
        self._free_slots.append(slot)

    # -- host swap tier ------------------------------------------------------
    def _swap_gather(self, slot: int, gids, lids) -> Pytree:
        """Pull `slot`'s named blocks (and state row) off the device as one
        pytree congruent to the pool with the block dim shrunk to n —
        quant scale leaves ride along automatically."""
        gi = jnp.asarray(np.asarray(gids, np.int32))
        li = jnp.asarray(np.asarray(lids, np.int32))

        def kv(dst, is_local, is_scale, axis):
            ids = li if is_local else gi
            return dst[:, ids] if axis == 1 else dst[ids]

        def state(dst, axis):
            return jax.lax.dynamic_slice_in_dim(dst, slot, 1, axis=axis)

        f = Mo._paged_kv_op(self.caches, self.cfg, kv, state)
        return jax.tree_util.tree_map_with_path(f, self.caches)

    def _swap_scatter(self, slot: int, gids, lids, payload: Pytree) -> None:
        """Scatter a host payload back into freshly allocated blocks (the
        inverse of _swap_gather, new physical ids)."""
        gi = jnp.asarray(np.asarray(gids, np.int32))
        li = jnp.asarray(np.asarray(lids, np.int32))

        def kv(dst, is_local, is_scale, axis, src):
            ids = li if is_local else gi
            src = jnp.asarray(src).astype(dst.dtype)
            if axis == 1:
                return dst.at[:, ids].set(src)
            return dst.at[ids].set(src)

        def state(dst, axis, src):
            return jax.lax.dynamic_update_slice_in_dim(
                dst, jnp.asarray(src).astype(dst.dtype), slot, axis=axis)

        f = Mo._paged_kv_op(self.caches, self.cfg, kv, state)
        self.caches = jax.tree_util.tree_map_with_path(
            f, self.caches, payload)

    def swap_out(self, slot: int) -> bool:
        """Copy `slot`'s live KV (every allocated block + state row) to the
        host pool, then evict the slot. Returns False — caller falls back
        to restart preemption — when no host pool is attached, the budget
        is exhausted, or the slot is still prefilling (partial-prompt lane
        state doesn't restore; restart is the correct path there). Shared
        prefix blocks are copied too: the restore allocates private blocks,
        trading dedup for zero recompute (the index keeps the originals)."""
        if self.swap_pool is None:
            return False
        s = self._slots[slot]
        assert s is not None
        if s.prefilling:
            return False
        n_blocks = s.alloc_g + s.alloc_l
        if not self.swap_pool.can_store(n_blocks):
            return False
        gids = self.table[slot, :s.alloc_g].copy()
        lids = self.table_local[slot, :s.alloc_l].copy()
        payload = jax.device_get(self._swap_gather(slot, gids, lids))
        nbytes = int(sum(x.nbytes for x in jax.tree.leaves(payload)))
        self.swap_pool.store(SwapRecord(
            rid=s.rid, payload=payload, n_blocks=n_blocks, nbytes=nbytes,
            cur_len=s.cur_len, tokens_done=s.tokens_done, gen_len=s.gen_len,
            reserved=s.reserved, cached_len=s.cached_len,
            alloc_g=s.alloc_g, alloc_l=s.alloc_l, plen=s.plen))
        self.evict(slot)
        self._swap_out_bytes += nbytes
        self._swapped_blocks += n_blocks
        return True

    def has_swapped(self, rid: int) -> bool:
        return self.swap_pool is not None and self.swap_pool.has(rid)

    def plan_resume(self, rid: int) -> bool:
        """Reserve `rid`'s swap-in footprint ahead of fresh admissions.

        Opportunistic can_resume probes race every tick against fresh
        arrivals with tighter deadlines: the victim only ever resumes in a
        tick where its whole footprint happens to be free at probe time —
        under a steady EDF stream of fresh work, possibly never. A plan
        is a standing reservation (counted in _reserved_total, shrinking
        free_unreserved) taken the moment capacity exists, so fresh
        admissions queue behind the victim instead of starving it. One
        backend fleet-wide may hold the plan (HostSwapPool arbitrates);
        swap_in consumes it. Returns True iff this backend now holds the
        plan. Idempotent — a standing plan re-probes for free."""
        if not self.has_swapped(rid):
            return False
        if rid in self._resume_plans:
            return True
        if self.swap_pool.planner(rid) is not None:
            return False  # another replica already reserved the resume
        rec = self.swap_pool.peek(rid)
        need = rec.n_blocks + rec.reserved
        if need > self.free_unreserved:
            return False
        self._resume_plans[rid] = need
        self._reserved_total += need
        self.swap_pool.plan(rid, self)
        return True

    def cancel_resume_plans(self) -> None:
        """Release every standing resume reservation (drain/release path:
        a retiring backend must not pin capacity for resumes it will never
        run — the swapped records stay in the shared pool, and a live
        peer can take over the plan next tick)."""
        for rid, need in list(self._resume_plans.items()):
            self._reserved_total -= need
            self.swap_pool.unplan(rid)
        self._resume_plans.clear()

    def can_resume(self, rid: int) -> bool:
        """Swap-in admission math: a free slot plus the request's allocated
        blocks AND its unspent reservation (it must still be able to finish
        its declared gen_len without deadlocking mid-decode). With a
        standing plan here the blocks are already reserved — only the slot
        is still in question; a plan held by another backend makes the rid
        theirs to resume."""
        if not self.has_swapped(rid) or not self._free_slots:
            return False
        planner = self.swap_pool.planner(rid)
        if planner is not None:
            return planner is self
        rec = self.swap_pool.peek(rid)
        return rec.n_blocks + rec.reserved <= self.free_unreserved

    def swap_in(self, rid: int) -> int:
        """Restore a swapped request: allocate fresh blocks, scatter the
        host payload back, rebuild the PagedSlot at its swap-point cursor.
        The restored KV is byte-identical to what swap_out pulled (numpy
        round-trips bf16/int8 losslessly), so decoding resumes bit-
        identically; restored blocks are private (shared_g=0, no hashes —
        re-registration would alias the index's live originals)."""
        assert self.can_resume(rid), f"cannot resume swapped rid {rid}"
        planned = self._resume_plans.pop(rid, None)
        if planned is not None:  # consume the standing reservation
            self._reserved_total -= planned
        rec = self.swap_pool.take(rid)
        slot = self._free_slots.popleft()
        need = rec.reserved + rec.alloc_g + rec.alloc_l
        s = PagedSlot(rid=rid, cur_len=rec.cur_len,
                      tokens_done=rec.tokens_done, gen_len=rec.gen_len,
                      plen=rec.plen, reserved=need, cached_len=rec.cached_len)
        self._slots[slot] = s
        self._reserved_total += need
        for _ in range(rec.alloc_g):  # _alloc draws the reservation down
            self._alloc(slot, local=False)
        for _ in range(rec.alloc_l):
            self._alloc(slot, local=True)
        self._swap_scatter(slot, self.table[slot, :rec.alloc_g],
                           self.table_local[slot, :rec.alloc_l], rec.payload)
        self._swap_in_bytes += rec.nbytes
        return slot

    def drop_swapped(self, rid: int) -> None:
        """Discard `rid`'s host copy (restart fallback / cancellation) and
        release any standing resume reservation held for it here."""
        if self.swap_pool is not None:
            planned = self._resume_plans.pop(rid, None)
            if planned is not None:
                self._reserved_total -= planned
            self.swap_pool.drop(rid)

    def cached_prefix_len(self, slot: int) -> int:
        """Prompt positions this slot serves from the prefix cache — the
        engine starts the request's prefill lanes here."""
        s = self._slots[slot]
        return 0 if s is None else s.cached_len

    def probe_prefix(self, prompt) -> int:
        """Prompt positions an admission would serve from the cache right
        now (read-only). The router's prefix-affine policy probes every
        replica's pool with this before choosing one."""
        if prompt is None:
            return 0
        return self._probe(prompt)[2]

    def release(self) -> None:
        """Retire the pool (replica scale-down). Verifies the free-list
        accounting returns to empty — every usable block either free or
        cache-retained with zero references, no dangling reservations —
        then drops the device cache pytree. Leaks raise: a drained
        replica that cannot account for all its blocks is exactly the bug
        refcounting must never hide."""
        live = [i for i, s in enumerate(self._slots) if s is not None]
        if live:
            raise RuntimeError(f"release with occupied slots {live}")
        if self.swap_pool is not None:
            # standing resume reservations are not leaks: the records stay
            # in the shared pool for a live peer to plan next tick
            self.cancel_resume_plans()
        if self._reserved_total:
            raise RuntimeError(f"release leaked {self._reserved_total} "
                               "reserved blocks")
        accounted = len(self._free_blocks) + len(self._reclaim)
        if accounted != self.usable_blocks:
            raise RuntimeError(
                f"release leaked {self.usable_blocks - accounted} blocks "
                f"({len(self._free_blocks)} free + {len(self._reclaim)} "
                f"reclaimable of {self.usable_blocks})")
        if int(np.count_nonzero(self._ref)):
            held = np.flatnonzero(self._ref).tolist()
            raise RuntimeError(f"release with referenced blocks {held}")
        self.caches = None
        if self.swap_pool is not None:
            # last backend off a shared pool leak-checks host residency
            pool, self.swap_pool = self.swap_pool, None
            pool.detach()

    # -- reporting ----------------------------------------------------------
    @property
    def prefix_hit_tokens(self) -> int:
        """Cumulative prompt tokens served from the cache — the fleet
        rollup sums these raw counts across replicas (a mean of
        per-replica *ratios* would let zero-traffic replicas drag the
        fleet rate down)."""
        return self._hit_tokens

    @property
    def prefix_lookup_tokens(self) -> int:
        """Cumulative prompt tokens probed at admission (the hit-rate
        denominator)."""
        return self._lookup_tokens

    @property
    def prefix_hit_rate(self) -> float:
        """Cumulative fraction of probed prompt tokens served from the
        cache (the prefill compute the pool saved)."""
        return self._hit_tokens / max(self._lookup_tokens, 1)

    @property
    def shared_occupancy(self) -> float:
        """Fraction of the pool *actively* shared — blocks referenced by
        two or more live requests right now. Deliberately not "registered
        blocks": unique-prompt traffic registers (and retains) every full
        prompt block without ever sharing one, and a scale-hold keyed on
        retention would pin the cluster at peak size under 0% hit rate.
        Only concurrent sharing can push a refcount past 1, so this signal
        decays to 0 as sharing traffic drains and the autoscaler's shrink
        paths reopen."""
        return (int(np.count_nonzero(self._ref >= 2))
                / max(self.usable_blocks, 1))

    def metrics(self) -> Dict[str, float]:
        """Backend load signals merged into the engine snapshot: committed
        blocks are the signal that actually gates admission; the prefix
        signals feed the autoscaler's scale-hold (core/autoscaler.py).
        Swap counters are cumulative and per-backend (each replica reports
        its own traffic even when the host pool is shared), so the fleet
        rollup can sum them without double counting."""
        m = {"kv_block_occupancy": self.block_occupancy,
             "prefix_hit_rate": self.prefix_hit_rate,
             "kv_shared_occupancy": self.shared_occupancy}
        if self.swap_pool is not None:
            m.update(swapped_blocks=float(self._swapped_blocks),
                     swap_out_bytes=float(self._swap_out_bytes),
                     swap_in_bytes=float(self._swap_in_bytes))
        return m

    def describe(self) -> str:
        swap = ("" if self.swap_pool is None else
                ", host swap on")
        return (f"paged KV: {self.num_blocks} blocks x "
                f"{self.block_size} tokens, prefix cache "
                f"{'on' if self.prefix_cache else 'off'}{swap}")

    # -- introspection (tests) ----------------------------------------------
    def read_slot(self, slot: int) -> Pytree:
        """Gather `slot` back as a batch-1 cache pytree; unallocated table
        entries read as zeros (the null block may hold masked-row junk)."""
        s = self._slots[slot]
        tg, tl = self._tables_of(slot)
        ag = 0 if s is None else s.alloc_g
        al = 0 if s is None else s.alloc_l
        valid = (np.arange(max(self.mb_global, 1)) < ag)
        valid_l = (np.arange(max(self.mb_local, 1)) < al)
        return self._read(self.caches, jnp.asarray(slot, jnp.int32), tg, tl,
                          jnp.asarray(valid), jnp.asarray(valid_l))


class QuantBlockManager(BlockManager):
    """The third KV backend (`--kv quant`): BlockManager bookkeeping over
    an int8 block pool with per-row f32 dequant scales ([NB,Hkv,bs] — one
    scale per (block, head, token) across the head dim).

    Everything host-side (tables, refcounts, prefix hashing, reservation
    math, swap) is inherited unchanged; the deltas are device-side:
    the pool layout (Mo.init_paged_cache quant=True), quantize-on-insert
    (prefill caches expand through Mo.quantize_paged_request inside the
    insert jit; the fused decode step quantizes each new token's K/V row
    in models/model.py, dispatching on the "k_scale" cache leaf), and
    dequant fused into the read path (Pallas kernel with the scales in
    VMEM blocks beside their int8 K/V blocks on TPU, gather+multiply XLA
    path on CPU).

    At ~(hd+4)/(2*hd) the bytes per token of the bf16 pool, an equal-byte
    budget holds ~2x the blocks — ~2x admitted concurrency — with
    bit-exactness relaxed to a bounded-divergence contract (see
    docs/serving.md): `kv_quant_divergence` below is the scheme's
    calibrated relative RMS quantization error."""

    kind = "quant"
    _quant = True

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        cfg, bs = self.cfg, self.block_size
        base_insert = Mo.make_paged_insert(cfg, bs)

        def quant_insert(pool, request, slot, tg, tl):
            return base_insert(pool, Mo.quantize_paged_request(cfg, request),
                               slot, tg, tl)

        self._insert = shared_jit(("quant_insert", cfg, bs),
                                  lambda: quant_insert, donate_argnums=(0,))
        # calibrated divergence: relative RMS error of the int8 scheme on a
        # unit-normal sample (the per-write measurement would sync the hot
        # path; the bounded-divergence test pins the end-to-end bound)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(1024, cfg.head_dim)), jnp.float32)
        from repro.kernels.paged_decode.ops import quantize_kv
        q, s = quantize_kv(x)
        deq = q.astype(jnp.float32) * s[..., None]
        self.quant_divergence = float(
            jnp.sqrt(jnp.mean((deq - x) ** 2) / jnp.mean(x ** 2)))

    def metrics(self) -> Dict[str, float]:
        m = super().metrics()
        m["kv_quant_divergence"] = self.quant_divergence
        return m

    def describe(self) -> str:
        return "int8 " + super().describe().replace("paged KV", "quant KV", 1)

    def read_slot(self, slot: int) -> Pytree:
        """Introspection reads dequantize (int8 * scales -> bf16) so the
        result is directly comparable to an fp pool's read."""
        return Mo.dequantize_paged_request(self.cfg, super().read_slot(slot))
