"""Paged KV-cache memory manager: block-granular pooling + prefix cache.

THE pool of the serving engine: ONE manager of slots, positions,
admission and preemption over one block array and one page table a
request FOR EACH CACHE GROUP of the model (``models/decoder_spec.py``:
the layers that share a cache descriptor and a window; GPT-2, A.X-K1 and
SDAR have one group, and everything below reads as it always did for
them; a group's layers are those that HOLD a cache — a layer whose mixer
is a state alone is in none, so a 10-layer model with 2 attention layers
has a block array of 2 layers, and a block's bytes and a token's count
those 2). A group's device array is ``[its layers, num_blocks + 1, heads,
block_size, lanes]`` (K|V folded into the lanes — the ONE layout every
reader of the pool uses, see ops/ragged_paged_attention.py): a request
owns only the blocks covering its tokens SO FAR, addressed through a
per-request page table that maps virtual cache index ``i`` to
``(table[i // block_size], i % block_size)``, so concurrency is bounded
by the tokens in flight and not by worst-case sequence length (the
Ragged-Paged-Attention argument, PAPERS.md). Physical block 0 of every
group is a reserved SCRATCH block — page-table padding points at it, pad
rows' garbage lands in it, and nothing ever reads it through an unmasked
position.

**A window group** (``window`` W > 0: its layers' rows see the last W
positions only) FREES, whenever a launch's rows have been dispatched
(``advance``), every block that lies wholly behind ``pos - W + 1``: the
table entry goes back to the scratch block, the virtual index does not
move, and the group's ``lo`` of the slot is the first position still
held — the kernel's walk starts at the window and never reads the freed
entries. A launch already dispatched may still read a block freed after
it: the device runs launches in order, and the block's next writer is a
later launch. Its blocks are allocated as rows are written
(``ensure_writable_range``), never for a whole prompt at admission, so a
slot holds ``W - 1 + n`` tokens of it while a chunk of ``n`` rows is
planned and ``ceil(W / block_size) + 1`` blocks at most between chunks.
With more than one group no block is offered to or matched in the
prefix cache (a block of the window-0 group alone does not let a request
skip its prompt: the window layers' last W - 1 tokens are gone), and
int8/fp8 blocks, the host tier and a mesh are refused.

**Recurrent state** (``state=``: the spec's layers that run a state-holding
mixer beside their attention or in place of it,
``models/decoder_spec.py:StateSpec``). What
a sequence holds there has a fixed size, so it is a ROW A SLOT, not
blocks a token: ``state_data`` is one device array a part of the
descriptor, ``[those layers, num_slots + 1, *shape]`` (the last row is
owned by no slot, as block 0 is by no request: ``ops/ssm.py`` parks a
chunked scan's running state there). A slot's row is claimed with the
slot (``alloc``) and given back with it (``free``); it is never cleared
on the host — the step program starts a sequence at position 0 from zero
itself, because the slot's previous owner may still have a launch in
flight. The arrays ride the donated step beside the block arrays, and
their bytes stand in the HBM ledger under ``<pool>/state``. A state has
no snapshot a block, so nothing is offered to or matched in the prefix
cache, and a preempted request is re-fed from position 0.

Host-side manager (this module, scheduler-thread-owned):

* **request slots** — the launch's batch axis: deterministic
  lowest-index allocation, per-slot ``pos`` (cache index of the next
  write) and, a group, ``lo`` (first valid index; 0 unless a window
  group has freed blocks — paged sequences are aligned
  at virtual index 0, so block contents depend only on the token prefix,
  which is what makes them shareable across requests);
* **free-list block allocator** — blocks move between the free list,
  request page tables (refcounted), and the prefix cache's LRU of
  released-but-reusable blocks;
* **page tables in pow2 buckets** — a launch's table width is the next
  power of two over the blocks its longest request holds (capped at
  ``max_table_len``), so there is one program per table bucket, never
  one per table length;
* **refcounts + copy-on-write** — a block reachable from several page
  tables (prefix sharing) is never written through; the manager's
  ``ensure_writable_range`` hands the engine ``(dst, src)`` copy orders
  and swaps the table entries, so appends always hit a refcount-1 block.
  By construction shared blocks sit strictly below every sharer's write
  position (reuse is capped at ``(len - 1) // block_size`` full
  blocks), so COW is a guard rail, not a hot path;
* **prefix-cache trie** — full token blocks are registered under their
  token-prefix key (the dict key IS the exact prefix tuple, so "hash"
  collisions cannot alias two different prefixes); a later request
  whose prompt starts with the same full blocks adopts their K/V and
  feeds only the uncovered tail, in chunks, through the fused step.
  Released cached blocks wait in an LRU; allocation pressure evicts the
  oldest refcount-0 entry (and unregisters its now-unreachable
  descendants) before giving up.

Monitor wiring (PR-1): ``serving/kv_blocks_in_use`` histogram,
``serving/prefix_hit`` / ``serving/prefix_miss`` /
``serving/prefill_tokens_saved`` / ``serving/prefix_evict`` counters
(``serving/preempt`` is counted by the scheduler's preemption path).

Threading contract: the manager is owned by the scheduler thread;
``alloc`` / ``free`` / ``set_slot`` are only called from it. ``data`` is
rebound by the engine after every donated step (the old array is deleted
by XLA — donation — so nothing else may hold it).
"""
from __future__ import annotations

import heapq
import itertools
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework.monitor import stat_add, stat_observe
from ..profiler import memory as _memory

__all__ = ["PagedKVPool", "PoolCapacityError", "PoolExhaustedError",
           "BlockError"]


# process-wide pool numbering for the HBM ledger keys (two engines in
# one process must not alias each other's ledger entries)
_pool_ids = itertools.count(1)


def _drop_pool_ledger(ledger_key: str) -> None:
    """weakref.finalize target for a pool's ledger entries — a module
    function so the finalizer holds no reference to the pool."""
    for part in ("capacity", "in_use", "state"):
        _memory.ledger_drop(f"{ledger_key}/{part}")


class PoolCapacityError(ValueError):
    """The request can NEVER fit this pool (virtual capacity or total
    block budget) — raised at ``submit()`` time, fail fast."""


class PoolExhaustedError(RuntimeError):
    """No free and no evictable block right now — a TRANSIENT pressure
    signal; the scheduler answers it by preempting the youngest active
    request, never by corrupting the free list."""


class BlockError(ValueError):
    """Block bookkeeping misuse (double free / unref of an unreferenced
    block) — named so tests can assert the free list was protected."""


class _PagedSlot:
    """Per-request decode state: the virtual position, and a cache group
    the page table and the first position still held."""

    __slots__ = ("pos", "los", "tables")

    def __init__(self, n_groups: int = 1):
        self.pos = 0
        self.los: List[int] = [0] * n_groups
        # physical block ids, virtual order, a group
        self.tables: List[List[int]] = [[] for _ in range(n_groups)]

    # the first group's, as every one-group caller reads them
    @property
    def table(self) -> List[int]:
        return self.tables[0]

    @table.setter
    def table(self, value: List[int]) -> None:
        self.tables[0] = value


class _BlockGroup:
    """One cache group's blocks: the device array, the free list and the
    refcounts. ``window`` 0 keeps a sequence's whole context."""

    __slots__ = ("index", "num_layers", "num_heads", "lanes", "window",
                 "num_blocks", "shape", "data", "free", "ref")

    def __init__(self, index, num_layers, num_heads, lanes, window,
                 num_blocks, block_size):
        self.index = int(index)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.lanes = int(lanes)
        self.window = int(window)
        self.num_blocks = int(num_blocks)
        # +1: physical block 0 is the reserved scratch block
        self.shape = (self.num_layers, self.num_blocks + 1, self.num_heads,
                      int(block_size), self.lanes)
        self.data = None
        # min-heap: deterministic lowest-id allocation at O(log n) —
        # unlike the slot list (num_slots entries), num_blocks is
        # production-large and a min()+remove() scan per block would
        # sit on the per-cycle hot path
        self.free: List[int] = list(range(1, self.num_blocks + 1))
        self.ref: Dict[int, int] = {}             # block -> request refs


class _TrieNode:
    """One cached full block. Keyed in ``_trie`` by the exact token
    prefix tuple it encodes (root..this block, inclusive)."""

    __slots__ = ("key", "block", "children")

    def __init__(self, key: Tuple[int, ...], block: int):
        self.key = key
        self.block = block
        self.children: set = set()      # child keys (one block longer)


class PagedKVPool:
    """Block-pooled KV cache + slot/page-table/prefix-cache manager.

    ``data`` is the FIRST cache group's jnp array ``[layers,
    num_blocks + 1, heads, block_size, 2 * head_dim]`` (block 0 = scratch;
    a row's lanes hold K then V); the engine threads every group's array
    (``group_data``) through the donated fused step and rebinds them
    here. ``num_slots`` bounds concurrent REQUESTS (the
    launch's batch axis), ``num_blocks`` bounds their total KV footprint
    — with mixed lengths the block budget, not the slot count, is what
    fills first. The positional shape arguments, ``num_blocks``,
    ``lanes`` and ``window`` describe the first group; ``more_groups``
    the others, each a dict of ``num_layers``, ``num_heads``, ``lanes``,
    ``window`` and ``num_blocks`` (module doc). ``state`` is ``(layers
    with a recurrent state, ((name, shape, dtype), ...))``: one array a
    part, a row a slot (module doc).
    """

    #: storage dtypes quantized with per-block max-abs scales (the
    #: EQuARX per-chunk scheme of the PR-10 gradient wire, applied to
    #: KV blocks): int8 now, fp8 slots in when the backend has it
    _QUANT_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0}

    def __init__(self, num_layers: int, num_slots: int, num_heads: int,
                 max_len: int, head_dim: int, *, block_size: int = 16,
                 num_blocks: Optional[int] = None, dtype="float32",
                 mesh=None, mp_axis: str = "mp",
                 lanes: Optional[int] = None, window: int = 0,
                 more_groups=(), state=None):
        import jax.numpy as jnp

        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if block_size < 1 or (block_size & (block_size - 1)):
            raise ValueError(
                f"block_size must be a power of two, got {block_size}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        self.num_layers = int(num_layers)
        self.num_slots = int(num_slots)
        self.num_heads = int(num_heads)
        self.max_len = int(max_len)
        self.head_dim = int(head_dim)
        # a cached row's width: K|V side by side for a per-head row, or
        # what the model's cache descriptor states (a latent pool is
        # ``num_heads=1, lanes=descriptor's``: models/decoder_spec.py)
        self.lanes = int(lanes) if lanes else 2 * self.head_dim
        self.block_size = int(block_size)
        # blocks a single request can ever hold (covers [0, max_len))
        self.max_table_len = -(-self.max_len // self.block_size)
        if num_blocks is None:
            # worst-case budget: every slot could still go the full
            # max_len (callers shrink this to what the device holds —
            # admission then gates on blocks and pressure preempts)
            num_blocks = self.num_slots * self.max_table_len
        self.groups: List[_BlockGroup] = [_BlockGroup(
            0, self.num_layers, self.num_heads, self.lanes, window,
            num_blocks, self.block_size)]
        for g in more_groups:
            self.groups.append(_BlockGroup(
                len(self.groups), g["num_layers"], g["num_heads"],
                g["lanes"], g.get("window", 0), g["num_blocks"],
                self.block_size))
        for grp in self.groups:
            need = self._blocks_a_slot_needs(grp, self.max_len)
            if grp.num_blocks < need:
                raise ValueError(
                    f"num_blocks={grp.num_blocks} cannot hold even one "
                    f"max-length request ({need} blocks)")
        self.dtype = jnp.dtype(dtype)
        if len(self.groups) > 1 and (
                mesh is not None or self.dtype.name in self._QUANT_QMAX):
            raise ValueError(
                "more than one cache group over a mesh or over int8/fp8 "
                "blocks is not built")
        if state is not None and (
                mesh is not None or self.dtype.name in self._QUANT_QMAX):
            raise ValueError(
                "a recurrent state over a mesh or beside int8/fp8 blocks "
                "is not built")
        # blocks a window group gave back behind its window, lifetime
        self.window_blocks_freed = 0
        # tensor-parallel pool: the block array is head-partitioned over
        # a 1-D mp mesh ([.., H/mp, ..] per device) while every host
        # structure below — page tables, free list, refcounts, prefix
        # trie — stays replicated host-side, untouched by the mesh
        self.mesh = mesh
        self.mp_axis = str(mp_axis)
        self.shards = 1 if mesh is None else int(mesh.shape[self.mp_axis])
        if mesh is not None:
            if self.num_heads % self.shards:
                raise ValueError(
                    f"num_heads={self.num_heads} not divisible by mesh "
                    f"{self.mp_axis}={self.shards}")
            if jnp.dtype(dtype).name in self._QUANT_QMAX:
                raise ValueError(
                    f"quantized KV blocks (dtype={dtype}) are not "
                    f"supported on a tensor-parallel pool yet")
        # quantized block storage: per-block max-abs scales live in a
        # parallel [L, 2, num_blocks + 1, H] f32 array riding every
        # donated step beside the pool (gather steps multiply after the
        # pool read; the fused kernel dequantizes in-register off the
        # scalar-prefetch metadata). Scale 0 = untouched block, whose
        # dequantized content is the same zeros a fresh float pool holds.
        self.quantized = self.dtype.name in self._QUANT_QMAX
        self.qmax = self._QUANT_QMAX.get(self.dtype.name)
        self.scales_shape = (self.num_layers, 2, self.num_blocks + 1,
                             self.num_heads)
        self.scales = (jnp.zeros(self.scales_shape, jnp.float32)
                       if self.quantized else None)
        for grp in self.groups:
            grp.data = self._alloc_data(grp)
        # the recurrent state a slot (module doc): (name, array shape,
        # dtype) a part, and the arrays
        self.state_parts = ()
        if state is not None:
            n_layers, parts = state
            self.state_parts = tuple(
                (str(name), (int(n_layers), self.num_slots + 1)
                 + tuple(int(d) for d in shape), jnp.dtype(dtype))
                for name, shape, dtype in parts)
        self.state_data = self._alloc_state()
        # prefix cache (the first group's blocks; nothing is offered or
        # matched with more than one group): exact-prefix-keyed trie + LRU
        # of released blocks
        # (before the ledger entry below is published: its in-use figure
        # reads blocks_in_use -> _lru)
        self._trie: Dict[Tuple[int, ...], _TrieNode] = {}
        self._block_key: Dict[int, Tuple[int, ...]] = {}
        self._lru: "OrderedDict[Tuple[int, ...], _TrieNode]" = OrderedDict()
        # request slots: lowest-index-first keeps slot assignment
        # deterministic (tests and trace/debug output stay stable
        # across runs)
        import weakref
        self._free_slots: List[int] = list(range(self.num_slots))
        self._slots: Dict[int, _PagedSlot] = {}
        self.ledger_key = f"serving/kv_pool#{next(_pool_ids)}"
        # a pool dropped WITHOUT engine.close() (exception paths, tests
        # building pools directly) must not haunt crosscheck()/OOM
        # postmortems with phantom KV bytes — same finalizer discipline
        # as the hapi train-state ledger keys
        weakref.finalize(self, _drop_pool_ledger, self.ledger_key)
        self._update_ledger()
        # pool-local prefix stats (engine.stats() reads these without
        # scraping process-global monitor counters)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.tokens_saved = 0
        self.evictions = 0
        # hierarchical host-DRAM tier (host_tier.py), attached by the
        # engine when host_tier_bytes= is set: keys that just went
        # refcount-0 wait in _tier_pending until the scheduler's
        # once-per-cycle tier_tick() dispatches ONE batched demotion
        # gather for all of them (write-back, off the hot path)
        self.host_tier = None
        self._tier_pending: set = set()
        self.tier_hits = {"hbm": 0, "host": 0, "miss": 0}
        self.tier_degraded = 0

    # -- the first group's, as every one-group caller reads them ----------
    @property
    def data(self):
        return self.groups[0].data

    @data.setter
    def data(self, value) -> None:
        self.groups[0].data = value

    @property
    def shape(self):
        return self.groups[0].shape

    @property
    def num_blocks(self) -> int:
        return self.groups[0].num_blocks

    @property
    def _free(self) -> List[int]:
        return self.groups[0].free

    @_free.setter
    def _free(self, value: List[int]) -> None:
        self.groups[0].free = value

    @property
    def _ref(self) -> Dict[int, int]:
        return self.groups[0].ref

    @property
    def group_data(self) -> tuple:
        """Every group's block array, in group order: what the fused
        step donates and returns."""
        return tuple(grp.data for grp in self.groups)

    @group_data.setter
    def group_data(self, arrays) -> None:
        for grp, a in zip(self.groups, arrays):
            grp.data = a

    def _blocks_a_slot_needs(self, grp: _BlockGroup, n_tokens: int) -> int:
        """The most blocks of ``grp`` one sequence of ``n_tokens`` holds
        between two chunks: all of them, or the window's."""
        n = self.blocks_for(n_tokens)
        if grp.window:
            n = min(n, self.blocks_for(grp.window) + 1)
        return n

    def _alloc_data(self, grp: Optional[_BlockGroup] = None):
        """Fresh zeroed block array of a group — head-partitioned over
        the mesh's ``mp`` axis when this is a tensor-parallel pool (each
        device holds ``[L, NB+1, H/mp, bs, 2*Dh]``), a plain
        single-device array otherwise."""
        import jax
        import jax.numpy as jnp
        shape = (grp or self.groups[0]).shape
        if self.mesh is None:
            return jnp.zeros(shape, self.dtype)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(
            self.mesh, P(None, None, self.mp_axis, None, None))
        return jax.device_put(jnp.zeros(shape, self.dtype), sh)

    def _alloc_state(self) -> tuple:
        """Fresh zeroed state arrays, one a part (empty: no state)."""
        import jax.numpy as jnp
        return tuple(jnp.zeros(shape, dtype)
                     for _, shape, dtype in self.state_parts)

    @property
    def state_bytes(self) -> int:
        """Device bytes of the state arrays, every slot's row and the one
        no slot owns."""
        return sum(int(np.prod(shape)) * dtype.itemsize
                   for _, shape, dtype in self.state_parts)

    @property
    def state_slot_bytes(self) -> int:
        """Bytes ONE slot's state takes, every part and layer."""
        return self.state_bytes // (self.num_slots + 1)

    @property
    def state_live_bytes(self) -> int:
        """State held by the slots that requests own."""
        return self.n_active * self.state_slot_bytes

    # -- HBM ledger (profiler/memory.py) -----------------------------------
    def _update_ledger(self) -> None:
        """Publish capacity + in-use bytes into the process HBM ledger
        (the 'what we think is live' side of the ledger-vs-device
        crosscheck). Host dict stores only — called from alloc/free and
        the block hooks, all scheduler-thread, all sync-free."""
        _memory.ledger_set(f"{self.ledger_key}/capacity",
                           self.capacity_bytes)
        _memory.ledger_set(f"{self.ledger_key}/in_use", self.bytes_in_use)
        if self.state_parts:
            _memory.ledger_set(f"{self.ledger_key}/state", self.state_bytes)

    def drop_ledger(self) -> None:
        """Remove this pool's ledger entries (engine close): the pool
        array may outlive the engine object briefly, but a closed
        engine's pool is no longer an accounted owner."""
        _drop_pool_ledger(self.ledger_key)

    # -- request slots (the launch's batch axis) ---------------------------
    def alloc(self) -> Optional[int]:
        """Claim the lowest free slot, or None when the pool is full."""
        if not self._free_slots:
            return None
        slot = min(self._free_slots)
        self._free_slots.remove(slot)
        self._slots[slot] = _PagedSlot(len(self.groups))
        self._observe_state()
        self._update_ledger()
        _memory.mark("kv/alloc", pool=self.ledger_key, slot=slot,
                     in_use=self.bytes_in_use)
        return slot

    def free(self, slot: int) -> None:
        """Return ``slot`` to the free list and unref every block in its
        page table: refcount-0 cached blocks stay in the prefix cache
        (LRU, evictable), uncached ones return to the free list. Device
        rows are NOT cleared — attention never looks past ``pos``, so
        stale K/V are unreachable by construction."""
        if slot not in self._slots:
            raise ValueError(f"slot {slot} is not allocated")
        st = self._slots.pop(slot)
        for grp, table in zip(self.groups, st.tables):
            for b in table:
                if b:                     # 0: freed behind the window
                    self._unref(b, grp)
        self._observe()
        self._free_slots.append(slot)
        self._observe_state()
        self._update_ledger()
        _memory.mark("kv/free", pool=self.ledger_key, slot=slot,
                     in_use=self.bytes_in_use)

    def _observe_state(self) -> None:
        """A slot IS its state row: claimed and given back with it."""
        if self.state_parts:
            stat_observe("serving/state_slots_in_use", self.n_active)

    def is_allocated(self, slot: int) -> bool:
        return slot in self._slots

    @property
    def n_active(self) -> int:
        return len(self._slots)

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    def active_slots(self) -> List[int]:
        return sorted(self._slots)

    def set_slot(self, slot: int, *, pos: int, lo: int) -> None:
        st = self._slots[slot]
        if not 0 <= lo <= pos < self.max_len:
            raise ValueError(
                f"slot {slot}: bad position state lo={lo} pos={pos} "
                f"(max_len={self.max_len})")
        st.pos = int(pos)
        st.los = [int(lo)] * len(self.groups)

    def advance(self, slot: int, n: int = 1) -> int:
        """``n`` tokens landed (a decode row, or one prefill chunk of
        the fused ragged step): the slot's write position moves ``n``
        cache indices later. ``n`` is a SIGNED delta — the
        speculative-decoding scheduler rolls back the rows a rejected
        draft wrote with a negative ``n`` (page tables address by
        ``pos``, so rollback is pure bookkeeping: the stale K/V beyond
        the new ``pos`` are masked out of attention and overwritten by
        the next append). Under block generation
        (``models/decoder_spec.py``) the scheduler calls this at a block's
        COMMIT pass only, with the block's length: a denoising pass leaves
        ``pos`` where it is, and the block's rows ``[pos, pos + B)`` stay
        writable (``ensure_writable_range``) and are rewritten every pass.
        Returns the new ``pos``."""
        if n == 0:
            raise ValueError("advance needs n != 0")
        st = self._slots[slot]
        new_pos = st.pos + int(n)        # validate BEFORE mutating: a
        if new_pos >= self.max_len:      # rejected advance must leave
            raise RuntimeError(          # the slot state untouched
                f"slot {slot} overran the virtual capacity "
                f"{self.max_len} — the admission check "
                f"(prompt + max_new <= max_len) is broken")
        if new_pos < max(st.los):
            raise RuntimeError(
                f"slot {slot}: rollback below the slot's floor "
                f"(pos={new_pos} < lo={max(st.los)}) — a speculative "
                f"rollback may only unwind rows written this cycle")
        st.pos = new_pos
        self._free_behind_window(st)
        return st.pos

    def _free_behind_window(self, st: _PagedSlot) -> None:
        """A window group keeps ``[pos - W + 1, pos)`` of a sequence and
        what the next rows add: every block wholly behind that goes back
        to the free list, its table entry to the scratch block, and the
        group's ``lo`` moves to the first position still held."""
        freed = 0
        for grp in self.groups:
            if not grp.window:
                continue
            g, table = grp.index, st.tables[grp.index]
            keep = min(max(0, st.pos - grp.window + 1) // self.block_size,
                       len(table))
            for vb in range(st.los[g] // self.block_size, keep):
                self._unref(table[vb], grp)
                table[vb] = 0
                freed += 1
            st.los[g] = max(st.los[g], keep * self.block_size)
        if freed:
            self.window_blocks_freed += freed
            self._observe()

    def slot_pos(self, slot: int) -> int:
        return self._slots[slot].pos

    def slot_lo(self, slot: int, group: int = 0) -> int:
        """First position of the slot that ``group`` still holds."""
        return self._slots[slot].los[group]

    def reset_data(self) -> None:
        """Reallocate the (donated, possibly already-deleted) device
        pool AND drop every cached block: zeroed device rows no longer
        match any trie key, so serving a prefix hit off them would
        replay garbage. Called by the scheduler's failure path after
        every in-flight slot has been failed and freed."""
        import jax.numpy as jnp
        if self._slots:
            raise RuntimeError(
                "reset_data with live slots: fail and free them first")
        for grp in self.groups:
            grp.data = self._alloc_data(grp)
            grp.ref.clear()
            grp.free = list(range(1, grp.num_blocks + 1))
        if self.quantized:
            self.scales = jnp.zeros(self.scales_shape, jnp.float32)
        self.state_data = self._alloc_state()
        self._trie.clear()
        self._block_key.clear()
        self._lru.clear()
        # pending demotions point at the old (possibly deleted) device
        # array — drop them; already-DEMOTED host copies stay valid
        # (content is a pure function of the prefix key)
        self._tier_pending.clear()
        self._observe()

    # -- block bookkeeping -------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Blocks covering virtual indices [0, n_tokens)."""
        return -(-int(n_tokens) // self.block_size)

    @property
    def blocks_in_use(self) -> int:
        """Blocks referenced by at least one page table (scratch and
        cached-but-released blocks excluded)."""
        return self.num_blocks - len(self._free) - len(self._lru)

    @property
    def blocks_available(self) -> int:
        """Free plus evictable (released cached) blocks."""
        return len(self._free) + len(self._lru)

    def group_blocks_in_use(self, group: int) -> int:
        """Blocks of ``group`` that page tables reference."""
        grp = self.groups[group]
        return grp.num_blocks - len(grp.free) \
            - (len(self._lru) if group == 0 else 0)

    def group_block_bytes(self, group: int) -> int:
        """Device bytes of ONE block of ``group`` across its layers."""
        grp = self.groups[group]
        return int(np.prod(grp.shape)) * self.dtype.itemsize \
            // self.shards // (grp.num_blocks + 1)

    @property
    def live_bytes(self) -> int:
        """Bytes of the blocks live page tables hold, every group."""
        if len(self.groups) == 1:
            return self.bytes_in_use
        return sum(self.group_blocks_in_use(g) * self.group_block_bytes(g)
                   for g in range(len(self.groups)))

    @property
    def live_tokens(self) -> int:
        """Tokens of the live sequences' contexts (sum of ``pos``)."""
        return sum(st.pos for st in self._slots.values())

    @property
    def cached_blocks(self) -> int:
        """Blocks currently registered in the prefix cache (referenced
        or waiting in the LRU)."""
        return len(self._trie)

    @property
    def block_storage_bytes(self) -> int:
        """PER-DEVICE bytes of the quantized-or-not block array alone —
        a tensor-parallel pool holds ``1/mp`` of the heads on each
        device, so the ledger (and every byte figure derived here)
        bills what ONE chip actually stores."""
        return sum(int(np.prod(grp.shape)) for grp in self.groups) \
            * self.dtype.itemsize // self.shards

    @property
    def scales_bytes(self) -> int:
        """Device bytes of the per-block scale array (0 for float
        pools)."""
        if not self.quantized:
            return 0
        return int(np.prod(self.scales_shape)) * 4

    @property
    def capacity_bytes(self) -> int:
        """Device bytes of the whole pool — block storage PLUS the
        per-block scale array of a quantized pool, so the same-byte-
        budget capacity comparison against a float pool stays honest."""
        return self.block_storage_bytes + self.scales_bytes

    @property
    def block_bytes(self) -> int:
        """Device bytes of ONE block across every layer/kv plane —
        scale bytes included for quantized pools (the quantum the HBM
        ledger accounts paged usage in)."""
        if len(self.groups) > 1:
            return self.group_block_bytes(0)
        return self.capacity_bytes // (self.num_blocks + 1)

    @classmethod
    def blocks_within_budget(cls, budget_bytes: int, *, num_layers: int,
                             num_heads: int, block_size: int,
                             head_dim: int = 0, dtype="float32",
                             lanes: Optional[int] = None) -> int:
        """Largest ``num_blocks`` whose pool (scratch block and, for
        quantized dtypes, the per-block scale array included) fits
        ``budget_bytes`` — the same-byte-budget sizing rule the
        capacity tests and ``--kv-dtype`` comparisons use. An int8 pool
        packs ~4x the blocks of an fp32 pool into the same budget
        (minus the f32 scale overhead of ``1 / (block_size *
        head_dim)``)."""
        import jax.numpy as jnp
        itemsize = jnp.dtype(dtype).itemsize
        lanes = int(lanes) if lanes else 2 * int(head_dim)
        per_block = num_layers * num_heads * block_size * lanes * itemsize
        if jnp.dtype(dtype).name in cls._QUANT_QMAX:
            per_block += num_layers * 2 * num_heads * 4
        # num_blocks + 1 physical blocks (scratch) must fit
        return max(0, int(budget_bytes) // per_block - 1)

    @property
    def bytes_in_use(self) -> int:
        """Bytes claimed by live requests: only blocks referenced by
        page tables count (every group's)."""
        if len(self.groups) > 1:
            return self.live_bytes
        return self.blocks_in_use * self.block_bytes

    def can_admit(self, n_tokens: int) -> bool:
        """Admission gate: enough free + evictable blocks IN EVERY GROUP
        to hold the request's first ``n_tokens`` tokens (a window group:
        its window's). Growth past that is the
        preemption policy's problem, so a head request never waits for
        its WORST case — the whole point of paging."""
        return self.blocks_available >= self._blocks_a_slot_needs(
            self.groups[0], n_tokens) and all(
            len(grp.free) >= self._blocks_a_slot_needs(grp, n_tokens)
            for grp in self.groups[1:])

    def _observe(self) -> None:
        stat_observe("serving/kv_blocks_in_use", self.blocks_in_use)
        for grp in self.groups[1:]:
            stat_observe(f"serving/kv_blocks_in_use/g{grp.index}",
                         self.group_blocks_in_use(grp.index))
        # block-granular HBM ledger refresh: _observe already fires at
        # every block-count change (alloc/unref/evict/free/reset)
        self._update_ledger()

    def _alloc_block(self, grp: Optional[_BlockGroup] = None) -> int:
        grp = grp or self.groups[0]
        if not grp.free:
            if grp.index:                # only the first group has a trie
                raise PoolExhaustedError(
                    f"all {grp.num_blocks} blocks of cache group "
                    f"{grp.index} are referenced")
            self._evict_one()            # raises PoolExhaustedError
        b = heapq.heappop(grp.free)      # deterministic, like slot alloc
        grp.ref[b] = 1
        if self.quantized:
            # a recycled block carries its previous tenant's per-block
            # max-abs scale, and _quant_append only GROWS scales
            # (scatter-max) — growth appends into this block would
            # quantize fresh K/V at an arbitrarily coarse stale scale.
            # Zero it at allocation (prefill rewrites it anyway;
            # LRU-adopted cached blocks never pass through here, so
            # their valid scales survive). Lazy device op, no sync.
            self.scales = self.scales.at[:, :, b].set(0.0)
        return b

    def _unref(self, b: int, grp: Optional[_BlockGroup] = None) -> None:
        grp = grp or self.groups[0]
        rc = grp.ref.get(b, 0)
        if rc <= 0:
            raise BlockError(
                f"block {b}{f' of cache group {grp.index}' * bool(grp.index)}"
                f" is not referenced (double free would corrupt "
                f"the free list)")
        grp.ref[b] = rc - 1
        if rc == 1:
            # only the first group's blocks are ever in the prefix cache
            key = None if grp.index else self._block_key.get(b)
            if key is not None and key in self._trie:
                # released but cached: joins the LRU (most-recent end),
                # reusable by a later prefix hit until evicted
                self._lru[key] = self._trie[key]
                if self.host_tier is not None:
                    # write-back candidate: demoted at the next
                    # tier_tick() if still evictable then
                    self._tier_pending.add(key)
            else:
                heapq.heappush(grp.free, b)

    def _evict_one(self) -> None:
        """Reclaim the least-recently-released cached block (and drop
        its now-unreachable cached descendants)."""
        if not self._lru:
            raise PoolExhaustedError(
                f"all {self.num_blocks} blocks are referenced and the "
                f"prefix cache has nothing to evict")
        key = next(iter(self._lru))
        self._drop_node(key)
        self.evictions += 1
        stat_add("serving/prefix_evict")

    def _drop_node(self, key: Tuple[int, ...]) -> None:
        """Unregister the cached block at ``key`` and its subtree. A
        refcount-0 block returns to the free list; a block still held
        by a request merely loses its cache membership (its owner frees
        it normally later)."""
        node = self._trie.pop(key, None)
        if node is None:
            return
        self._lru.pop(key, None)
        self._block_key.pop(node.block, None)
        if self._ref.get(node.block, 0) == 0:
            heapq.heappush(self._free, node.block)
        parent = self._trie.get(key[:-self.block_size])
        if parent is not None:
            parent.children.discard(key)
        for child in list(node.children):
            self._drop_node(child)

    # -- hierarchical host tier (host_tier.py) -----------------------------
    @property
    def host_block_nbytes(self) -> int:
        """HOST bytes of one demoted block — FULL heads (a
        tensor-parallel pool's demotion gathers the global value, so
        the host entry is shard-agnostic), no scratch, no sharding
        divisor."""
        return (self.num_layers * self.num_heads * self.block_size
                * self.lanes * self.dtype.itemsize)

    @property
    def host_scale_nbytes(self) -> int:
        """Host bytes of one block's per-block scale row (0 for float
        pools)."""
        return self.num_layers * 2 * self.num_heads * 4 \
            if self.quantized else 0

    def attach_host_tier(self, tier) -> None:
        """Bind a :class:`~.host_tier.HostBlockPool` as the spill
        target for LRU-evicted refcount-0 blocks (engine ctor,
        ``host_tier_bytes=``). Eagerly compiles the tier's batched
        gather/scatter for every pow2 width it can ever use — a
        first-use compile would otherwise stall the scheduler thread
        (and every decode slot with it) for ~100ms mid-serving."""
        self.host_tier = tier
        m = 1
        while True:
            ids = np.zeros(m, np.int32)
            blk = self.data[:, ids]                    # demote gather
            self.data = self.data.at[:, ids].set(blk)      # adopt
            if self.quantized:
                sca = self.scales[:, :, ids]
                self.scales = self.scales.at[:, :, ids].set(sca)
            if m >= self.num_blocks:
                break
            m *= 2

    def tier_tick(self) -> None:
        """Once-per-cycle demotion pump (scheduler thread, start of
        cycle): batch every key that went refcount-0 since the last
        tick and is STILL evictable into ONE lazy device gather, and
        hand it to the tier's spiller thread. The gather
        ``data[:, ids]`` is an independent non-donated array whose
        value is captured before any later donated step can delete the
        pool storage, so the spiller's blocking copy never races XLA
        donation. Dispatch-only — no device sync on this thread."""
        tier = self.host_tier
        if tier is None or not self._tier_pending:
            return
        pending, self._tier_pending = self._tier_pending, set()
        keys = [k for k in pending if k in self._lru and not tier.has(k)]
        if not keys:
            return
        # pow2-pad the gather width (repeat the last id — the spiller
        # only reads the first len(keys) lanes): an eager gather
        # compiles once per distinct index length, and a per-batch
        # shape would put a fresh ~100ms XLA compile on the scheduler
        # thread every few cycles. Same bucket discipline as prefill.
        raw = [self._trie[k].block for k in keys]
        m = 1 << (len(raw) - 1).bit_length()
        ids = np.asarray(raw + [raw[-1]] * (m - len(raw)), np.int32)
        blk = self.data[:, ids]           # lazy batched gather
        sca = self.scales[:, :, ids] if self.quantized else None
        tier.spill(keys, blk, sca)

    def tier_match(self, tokens) -> Tuple[List[Tuple[int, ...]], int]:
        """Continue :meth:`match_prefix`'s walk into the HOST tier:
        the chain of demoted full blocks that extends the device-cached
        prefix of ``tokens`` (same proper-prefix cap). Returns
        ``(host_keys, covered_tokens)`` where ``covered_tokens`` counts
        the device+host contiguous coverage — the scheduler's
        promotion gate mirrors the engine's uncovered-tail heuristic
        with it. Read-only."""
        tier = self.host_tier
        if tier is None:
            return [], 0
        toks = tuple(int(t) for t in tokens)
        bs = self.block_size
        host_keys: List[Tuple[int, ...]] = []
        covered = 0
        for i in range(1, (len(toks) - 1) // bs + 1):
            key = toks[:i * bs]
            if key in self._trie:
                covered = i * bs
                continue
            if tier.has(key):
                host_keys.append(key)
                covered = i * bs
            else:
                break
        return host_keys, covered

    def adopt_promotion(self, ticket) -> bool:
        """Land a staged promotion (scheduler thread, the cycle the
        ticket's H2D copy completed): allocate device blocks, scatter
        the staged batch into them (lazy ``.at[].set`` — no new trace
        site, no sync), and republish each key as a refcount-0 cached
        trie node, exactly as if the blocks had never been evicted.
        The content-canonical invariant makes every overlap safe: keys
        republished on the device while the copy staged are simply
        skipped (identical bytes), and exhaustion degrades to adopting
        the chain PREFIX that fits — or to a plain miss — never to an
        error on the serving path."""
        tier = self.host_tier
        if tier is None or ticket is None:
            return False
        if ticket.adopted:
            return True
        if ticket.failed or not ticket.staged_keys:
            tier.ticket_done(ticket)
            return False
        keep = [i for i, k in enumerate(ticket.staged_keys)
                if k not in self._trie]
        if not keep:
            # the whole chain was republished on the device while the
            # copy staged — identical bytes by the content-canonical
            # invariant, nothing to land
            ticket.adopted = True
            tier.ticket_done(ticket)
            return True
        ids: List[int] = []
        try:
            for _ in keep:
                ids.append(self._alloc_block())
        except PoolExhaustedError:
            pass                          # adopt the prefix that fits
        keep = keep[:len(ids)]
        if not keep:
            self.tier_degraded += 1
            stat_add("serving/tier_degraded")
            tier.ticket_done(ticket)
            return False
        # uniform pow2-wide gather + scatter, whatever subset of the
        # chain is being landed: the staged batch is already pow2-padded
        # (promoter side), and padding BOTH index vectors by repeating
        # their last entry keeps every adoption on one compiled shape
        # per bucket — duplicate scatter lanes write identical bytes,
        # so the result is unchanged. Without this, each distinct chain
        # length would eagerly compile a fresh gather/scatter pair on
        # the scheduler thread, stalling decode for ~100ms a pop.
        m = int(ticket.staged.shape[1])
        sel = np.asarray(keep + [keep[-1]] * (m - len(keep)), np.int32)
        idx = np.asarray(ids + [ids[-1]] * (m - len(ids)), np.int32)
        blk = ticket.staged[:, sel]
        sca = ticket.staged_scales
        self.data = self.data.at[:, idx].set(blk)
        if self.quantized and sca is not None:
            # adopted blocks carry their ORIGINAL per-block scales —
            # overwrite the zeros _alloc_block just staged
            self.scales = self.scales.at[:, :, idx].set(sca[:, :, sel])
        for k_i, b in zip(keep, ids):
            key = ticket.staged_keys[k_i]
            self._ref[b] = 0              # cache-resident, unreferenced
            node = _TrieNode(key, b)
            self._trie[key] = node
            self._block_key[b] = key
            parent = self._trie.get(key[:-self.block_size])
            if parent is not None:
                parent.children.add(key)
            self._lru[key] = node         # evictable until admitted
        ticket.adopted = True
        tier.note_promoted(ticket, len(keep))
        tier.ticket_done(ticket)
        self._observe()
        return True

    def note_tier_hit(self, kind: str) -> None:
        """Classify one admission for the tiered hit split: ``hbm``
        (device trie hit), ``host`` (hit served through a promotion),
        or ``miss``. Counted by the engine on every paged admission so
        the split keys exist tier or no tier."""
        self.tier_hits[kind] = self.tier_hits.get(kind, 0) + 1
        stat_add(f"serving/tier_hit_{kind}")

    # -- admission: prefix matching + table setup --------------------------
    def match_prefix(self, tokens) -> List[int]:
        """Longest chain of cached full blocks covering a PROPER prefix
        of ``tokens`` — capped at ``(len - 1) // block_size`` blocks so
        at least one token is always recomputed (its forward pass is
        what produces the next-token logits, and the cap is also what
        keeps every write strictly past the shared region, making COW a
        guard rail instead of a hot path). Returns the physical block
        ids, longest match first-to-last. Read-only. With more than one
        cache group, or a recurrent state, nothing is matched (module
        doc)."""
        if len(self.groups) > 1 or self.state_parts:
            return []
        toks = tuple(int(t) for t in tokens)
        bs = self.block_size
        blocks: List[int] = []
        for i in range(1, (len(toks) - 1) // bs + 1):
            node = self._trie.get(toks[:i * bs])
            if node is None:
                break
            blocks.append(node.block)
        return blocks

    def admit_cached(self, slot: int, blocks: List[int]) -> None:
        """Seed the slot's page table with matched prefix blocks
        (refcount++ each; a block leaves the LRU while referenced)."""
        st = self._require(slot)
        if st.table:
            raise BlockError(f"slot {slot} already has a page table")
        for b in blocks:
            rc = self._ref.get(b, 0)
            self._ref[b] = rc + 1
            if rc == 0:
                self._lru.pop(self._block_key.get(b), None)
        st.table = list(blocks)
        self.prefix_hits += 1
        self.tokens_saved += len(blocks) * self.block_size
        stat_add("serving/prefix_hit")
        stat_add("serving/prefill_tokens_saved",
                 len(blocks) * self.block_size)
        self._observe()

    def admit_fresh(self, slot: int, n_tokens: int) -> List[int]:
        """Allocate the page table covering ``[0, n_tokens)`` for a
        prefix-miss prefill. All-or-nothing: on exhaustion the partial
        allocation is rolled back and :class:`PoolExhaustedError`
        propagates (admission re-tries next cycle)."""
        st = self._require(slot)
        if any(st.tables):
            raise BlockError(f"slot {slot} already has a page table")
        # a window group's blocks come as its rows are written
        # (ensure_writable_range), never for a whole prompt
        done: List[_BlockGroup] = []
        try:
            for grp in self.groups:
                if grp.window:
                    continue
                done.append(grp)
                st.tables[grp.index] = mine = []
                for _ in range(self.blocks_for(n_tokens)):
                    mine.append(self._alloc_block(grp))
        except PoolExhaustedError:
            for grp in done:
                for b in st.tables[grp.index]:
                    self._unref(b, grp)
                st.tables[grp.index] = []
            raise
        got = st.table
        self.prefix_misses += 1
        stat_add("serving/prefix_miss")
        self._observe()
        return list(got)

    def register_prefix(self, slot: int, tokens) -> None:
        """Publish the slot's full token blocks into the prefix cache.
        Called after a prefill WROTE them; an existing entry for the
        same prefix stays canonical (this slot's duplicate block simply
        remains privately owned). With more than one cache group, or a
        recurrent state, nothing is offered (module doc)."""
        if len(self.groups) > 1 or self.state_parts:
            return
        st = self._require(slot)
        toks = tuple(int(t) for t in tokens)
        bs = self.block_size
        for i in range(len(toks) // bs):
            key = toks[:(i + 1) * bs]
            if key in self._trie:
                continue
            block = st.table[i]
            if block in self._block_key:
                continue                  # already published elsewhere
            self._trie[key] = _TrieNode(key, block)
            self._block_key[block] = key
            parent = self._trie.get(key[:-bs])
            if parent is not None:
                parent.children.add(key)

    def unpublish_from(self, slot: int, pos: int) -> None:
        """Drop any prefix-cache registration of the slot's blocks
        covering virtual index ``pos`` onward — the speculative-decode
        rollback guard: rows a rejected draft wrote must not leave a
        published block whose device content no longer matches its
        token-prefix key. Structurally the write path already unshares
        (COW) and unregisters (``_ensure_block``) before any write, so
        this is the same airtight-cheap insurance, called by the
        scheduler after a rollback."""
        st = self._require(slot)
        for vb in range(int(pos) // self.block_size, len(st.table)):
            key = self._block_key.get(st.table[vb])
            if key is not None:
                self._drop_node(key)

    # -- growth + copy-on-write --------------------------------------------
    def ensure_writable_range(self, slot: int,
                              last_pos: int) -> List[Tuple[int, int]]:
        """Before a launch writes a slot's rows: guarantee EVERY block
        covering virtual indices ``[pos, last_pos]`` exists and is exclusively
        owned (a chunk scatters a run of positions in one fused
        launch). Returns the copy-on-write ``(dst, src)`` orders, in
        virtual-block order. May raise :class:`PoolExhaustedError`
        mid-growth — already-granted blocks stay on the table (they are
        freed with the slot if the scheduler preempts it), and any COW
        orders collected BEFORE the failure ride on the exception as
        ``partial_cows``: the table swap already happened, so the
        caller must still perform those device copies — a retry after
        preemption sees the swapped (refcount-1) block and would never
        re-order the copy."""
        st = self._require(slot)
        if last_pos < st.pos:
            raise ValueError(
                f"slot {slot}: range end {last_pos} precedes pos {st.pos}")
        cows: List[Tuple[int, int]] = []
        for vb in range(st.pos // self.block_size,
                        last_pos // self.block_size + 1):
            try:
                cow = self._ensure_block(slot, st, vb)
                # the other groups' blocks are never shared: growth only
                for grp in self.groups[1:]:
                    table = st.tables[grp.index]
                    if vb > len(table):
                        raise RuntimeError(
                            f"slot {slot}: group {grp.index}'s page table "
                            f"has {len(table)} blocks but virtual block "
                            f"{vb} is needed")
                    if vb == len(table):
                        table.append(self._alloc_block(grp))
                        self._observe()
            except PoolExhaustedError as e:
                e.partial_cows = list(cows)
                raise
            if cow is not None:
                cows.append(cow)
        return cows

    def _ensure_block(self, slot: int, st: _PagedSlot,
                      vb: int) -> Optional[Tuple[int, int]]:
        if vb > len(st.table):
            raise RuntimeError(
                f"slot {slot}: page table has {len(st.table)} blocks but "
                f"virtual block {vb} is needed — positions outran "
                f"allocation")
        if vb == len(st.table):
            st.table.append(self._alloc_block())
            self._observe()
            return None
        b = st.table[vb]
        if self._ref.get(b, 0) > 1:
            nb = self._alloc_block()      # may raise: caller preempts
            st.table[vb] = nb
            self._unref(b)
            self._observe()
            return (nb, b)
        key = self._block_key.get(b)
        if key is not None:
            # about to append into a cached block in place: its content
            # will no longer match its prefix key, so unregister it
            # (structurally unreachable — reuse is capped below every
            # write position — but cheap to keep airtight)
            self._drop_node(key)
        return None

    def table_bucket(self, slot: int) -> int:
        """The slot's decode-trace bucket: next pow2 over its page-table
        length, capped at ``max_table_len`` — ONE decode trace per
        bucket, O(log max_table_len) buckets total."""
        n = max(1, len(self._require(slot).table))
        t = 1
        while t < n:
            t *= 2
        return min(t, self.max_table_len)

    def table_array(self, bucket: int, slots, group: int = 0) -> np.ndarray:
        """Dense int32 ``[num_slots, bucket]`` page-table operand of
        ``group`` for the decode step. Rows of slots outside ``slots``
        (and padding past a member's table, and a window group's freed
        entries) read 0 — the scratch block, whose
        gathered garbage the ``[lo, pos]`` mask hides and whose writes
        nobody reads."""
        out = np.zeros((self.num_slots, int(bucket)), np.int32)
        for slot in slots:
            table = self._require(slot).tables[group]
            if len(table) > bucket:
                raise RuntimeError(
                    f"slot {slot}: table length {len(table)} exceeds its "
                    f"bucket {bucket}")
            out[slot, :len(table)] = table
        return out

    def slot_table(self, slot: int, group: int = 0) -> List[int]:
        return list(self._require(slot).tables[group])

    def _require(self, slot: int) -> _PagedSlot:
        st = self._slots.get(slot)
        if st is None:
            raise ValueError(f"slot {slot} is not allocated")
        return st

    def __repr__(self):
        more = "".join(
            f" g{grp.index}(w{grp.window})="
            f"{self.group_blocks_in_use(grp.index)}/{grp.num_blocks}"
            for grp in self.groups[1:])
        return (f"<PagedKVPool blocks={self.blocks_in_use}/"
                f"{self.num_blocks}{more} x{self.block_size} "
                f"active={self.n_active}/{self.num_slots} "
                f"cached={len(self._trie)}>")
