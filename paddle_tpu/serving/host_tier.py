"""Host-DRAM spill tier for the paged prefix cache (hierarchical KV).

The prefix trie (``paging.py``) is HBM-bounded: at millions-of-users
scale the hot set of shared system prompts and few-shot preambles far
exceeds the device pool, so LRU-evicted refcount-0 blocks die and their
prefill work is repaid on the next hit. :class:`HostBlockPool` gives
those blocks a second life in a bounded host-DRAM store:

* **demotion (D2H, write-back)** — the scheduler thread dispatches ONE
  lazy batched gather per cycle over the blocks that just went
  refcount-0 (``PagedKVPool.tier_tick``) and hands the resulting
  independent device array to the SPILLER thread, which performs the
  blocking device→host copy off the decode hot path and files each
  block (plus its int8 per-block scale) under its exact token-prefix
  key. The gathered array is NOT the donated pool — its value is
  captured before any later donated step can delete the storage — so
  the spiller never races XLA donation.
* **promotion (H2D, double-buffered)** — a prefix hit on a demoted
  chain creates a :class:`PromotionTicket`; the PROMOTER thread stacks
  the chain into one contiguous batch ("Memory-efficient array
  redistribution", PAPERS.md: batch the copies, don't trickle blocks)
  and stages it with an async ``jax.device_put`` through a depth-2
  queue — the ``io.device_prefetch`` double-buffering idiom — so the
  H2D copy overlaps the decode cycles that keep running meanwhile. The
  scheduler treats the waiting request like a pending feed: decode
  slots are never blocked, and the request admits the cycle its blocks
  land (``PagedKVPool.adopt_promotion`` scatters the staged batch into
  freshly allocated device blocks and republishes the trie nodes).

Content-canonical invariant: every device write path either
copies-on-write or unregisters the trie key first, so a published key's
block content is a pure function of the key. Host copies inherit that —
a demoted block filed under key K can be adopted at ANY later time and
is bit-identical to a never-evicted block for K (fp32 and int8+scales),
which is what makes the demotion-vs-republish race and keeping the host
copy after promotion both safe.

Capacity is a ledger of its own: entries are billed block+scale bytes
against ``capacity_bytes`` with LRU eviction inside the tier, published
under ``host/``-prefixed keys so the HBM ledger-vs-device crosscheck
(``profiler/memory.py``) reports host bytes separately and
``plan_replica()`` never bills host DRAM against the HBM budget.

Nothing on the serving path raises: a full tier, a full spill queue, or
a busy promoter degrades to plain eviction / a plain prefix miss and is
counted (``serving/tier_degraded``). The named errors
(:class:`HostTierError` / :class:`HostTierFullError`) fire only on API
misuse (oversized single entry, operating a closed tier).

Threading contract: ``spill`` / ``request_promotion`` / ``has`` /
``get`` are called from the scheduler thread; the spiller and promoter
threads touch only the host store under ``_lock`` plus their queues.
The ONE sanctioned device→host copy in the serving package is
:meth:`HostBlockPool._fetch` (``# lint: ok``) — it runs on the spiller
thread, off the decode hot path; ``serving-host-sync`` keeps it that
way by construction.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework import metrics as _metrics
from ..framework.monitor import _percentile, stat_add, stat_observe
from ..profiler import memory as _memory

__all__ = ["HostBlockPool", "PromotionTicket", "HostTierError",
           "HostTierFullError"]

# process-wide tier numbering for the host ledger keys (mirrors the
# pool-ledger discipline in paging.py)
_tier_ids = itertools.count(1)

_END = object()                      # queue sentinel (io.device_prefetch)


class HostTierError(RuntimeError):
    """Host-tier API misuse (operating a closed tier, malformed entry)
    — named so tests can assert the serving path never sees it."""


class HostTierFullError(HostTierError):
    """A single entry exceeds the tier's whole capacity — a
    configuration error, not a pressure signal (pressure is answered by
    the tier's own LRU eviction, silently)."""


def _drop_tier_ledger(ledger_key: str) -> None:
    """weakref.finalize target — module function so the finalizer holds
    no reference to the tier (paging.py idiom)."""
    _memory.ledger_drop(f"{ledger_key}/capacity")
    _memory.ledger_drop(f"{ledger_key}/in_use")


class PromotionTicket:
    """One in-flight H2D promotion of a contiguous chain of demoted
    blocks. Created by ``request_promotion`` (scheduler thread), staged
    by the promoter thread (``staged``/``staged_scales`` become device
    arrays, ``ready`` is set), adopted exactly once by
    ``PagedKVPool.adopt_promotion`` (scheduler thread again)."""

    __slots__ = ("keys", "staged_keys", "staged", "staged_scales",
                 "ready", "failed", "adopted", "created_at", "staged_at")

    def __init__(self, keys: List[Tuple[int, ...]]):
        self.keys = list(keys)           # requested chain, root-first
        self.staged_keys: List[Tuple[int, ...]] = []
        self.staged = None               # device [L, n, H, bs, 2*hd]
        self.staged_scales = None        # device [L, 2, n, H] or None
        self.ready = threading.Event()
        self.failed = False
        self.adopted = False
        self.created_at = time.perf_counter()
        self.staged_at: Optional[float] = None


class HostBlockPool:
    """Bounded host-DRAM store of demoted KV blocks, keyed by exact
    token-prefix tuples (the same keys as the device trie — no hashing,
    no aliasing). ``block_nbytes``/``scale_nbytes`` are the HOST bytes
    of one full-heads block (a tensor-parallel pool demotes the
    gathered full-heads value, so host entries are shard-agnostic)."""

    def __init__(self, capacity_bytes: int, block_nbytes: int, *,
                 scale_nbytes: int = 0, name: Optional[str] = None,
                 spill_depth: int = 4, promote_depth: int = 2):
        if capacity_bytes < 1:
            raise ValueError(
                f"capacity_bytes must be >= 1, got {capacity_bytes}")
        if block_nbytes < 1:
            raise ValueError(
                f"block_nbytes must be >= 1, got {block_nbytes}")
        self.capacity_bytes = int(capacity_bytes)
        self.block_nbytes = int(block_nbytes)
        self.scale_nbytes = int(scale_nbytes)
        self.entry_nbytes = self.block_nbytes + self.scale_nbytes
        if self.entry_nbytes > self.capacity_bytes:
            raise HostTierFullError(
                f"one block+scale entry is {self.entry_nbytes} bytes but "
                f"host_tier capacity is only {self.capacity_bytes} — the "
                f"tier could never hold a single block")
        self.name = name or f"serving/host_tier#{next(_tier_ids)}"
        # entries: key -> (np block [L,2,H,bs,hd], np scale [L,2,H]|None)
        self._store: "OrderedDict[Tuple[int, ...], tuple]" = OrderedDict()
        self._lock = threading.Lock()
        self._tickets: Dict[Tuple[int, ...], PromotionTicket] = {}
        # progress beacon: set on every ticket completion so an
        # otherwise-idle scheduler (no decode slots, only
        # promotion-waiters queued) can nap instead of hot-spinning
        self._progress = threading.Event()
        self._closed = False
        # counters (tier-owned; engine.stats() surfaces them)
        self.demoted_blocks = 0
        self.promoted_blocks = 0
        self.dropped_blocks = 0          # spill-queue-full degradations
        self.tier_evictions = 0          # host-LRU capacity evictions
        self.promo_shed = 0              # promoter-busy degradations
        self._promo_ms: "deque[float]" = deque(maxlen=512)
        self._demo_ms: "deque[float]" = deque(maxlen=512)
        # host ledger (host/ prefix: crosscheck() splits these out of
        # the device ledger-vs-HBM comparison)
        self.ledger_key = f"host/{self.name}"
        weakref.finalize(self, _drop_tier_ledger, self.ledger_key)
        _memory.ledger_set(f"{self.ledger_key}/capacity",
                           self.capacity_bytes)
        _memory.ledger_set(f"{self.ledger_key}/in_use", 0)
        # spiller: bounded so a slow host copy back-pressures into
        # plain eviction (degrade), never into the scheduler blocking
        self._spill_q: "queue.Queue" = queue.Queue(maxsize=spill_depth)
        # promoter: depth-2 = double buffering (io.device_prefetch) —
        # one chain staging on the copy engine while one waits adopted
        self._promo_q: "queue.Queue" = queue.Queue(maxsize=promote_depth)
        self._spiller = threading.Thread(
            target=self._spill_loop, name=f"{self.name}-spiller",
            daemon=True)
        self._promoter = threading.Thread(
            target=self._promote_loop, name=f"{self.name}-promoter",
            daemon=True)
        self._spiller.start()
        self._promoter.start()

    # -- capacity / introspection ------------------------------------------
    @property
    def blocks(self) -> int:
        with self._lock:
            return len(self._store)

    @property
    def bytes_in_use(self) -> int:
        with self._lock:
            return len(self._store) * self.entry_nbytes

    @property
    def capacity_blocks(self) -> int:
        return self.capacity_bytes // self.entry_nbytes

    def has(self, key: Tuple[int, ...]) -> bool:
        with self._lock:
            return key in self._store

    def get(self, key: Tuple[int, ...]):
        """The host copy under ``key`` as ``(block, scale)`` numpy
        arrays (scale None for float pools). Refreshes the tier LRU.
        Raises :class:`HostTierError` on a missing key — tests only;
        the serving path goes through tickets."""
        with self._lock:
            entry = self._store.get(key)
            if entry is None:
                raise HostTierError(f"key {key!r} is not host-resident")
            self._store.move_to_end(key)
            return entry

    # -- demotion (D2H) ----------------------------------------------------
    def spill(self, keys: List[Tuple[int, ...]], blocks_dev,
              scales_dev=None) -> bool:
        """Enqueue a batched demotion: ``blocks_dev`` is the lazy
        device gather ``[L, len(keys), H, bs, 2*hd]`` the scheduler
        dispatched (an independent array — NOT the donated pool), and
        ``scales_dev`` its ``[L, 2, len(keys), H]`` companion for
        quantized pools. Never blocks: a full spill queue degrades to
        plain eviction (the blocks simply die, as they did before the
        tier existed) and returns False."""
        if self._closed or not keys:
            return False
        item = (list(keys), blocks_dev, scales_dev, time.perf_counter())
        try:
            self._spill_q.put_nowait(item)
        except queue.Full:
            self.dropped_blocks += len(keys)
            stat_add("serving/tier_degraded", len(keys))
            return False
        return True

    def put(self, key: Tuple[int, ...], block: np.ndarray,
            scale: Optional[np.ndarray] = None) -> None:
        """Directly file one HOST block (tests / future disaggregation
        transport). Raises :class:`HostTierFullError` only when the
        single entry could never fit; capacity pressure evicts the
        tier's own LRU silently."""
        if self._closed:
            raise HostTierError(f"{self.name} is closed")
        with self._lock:
            self._put_locked(key, block, scale)
        self._update_ledger()

    def _put_locked(self, key, block, scale) -> None:
        if key in self._store:
            self._store.move_to_end(key)  # refreshed, content identical
            return
        while len(self._store) + 1 > self.capacity_blocks:
            self._store.popitem(last=False)
            self.tier_evictions += 1
        self._store[key] = (block, scale)
        self.demoted_blocks += 1

    def _fetch(self, dev) -> np.ndarray:
        """THE sanctioned device→host copy of the serving package: the
        batched demotion gather, materialized on the SPILLER thread off
        the decode hot path. An instance method so race tests can
        monkeypatch it to gate/instrument the copy."""
        import jax
        return np.asarray(jax.device_get(dev))  # lint: ok

    def _spill_loop(self) -> None:
        while True:
            item = self._spill_q.get()
            try:
                if item is _END:
                    return
                keys, blocks_dev, scales_dev, t0 = item
                try:
                    host = self._fetch(blocks_dev)
                    sca = (self._fetch(scales_dev)
                           if scales_dev is not None else None)
                except Exception:
                    # a failed copy (engine torn down mid-flight) is a
                    # degradation, never a crash on a daemon thread
                    self.dropped_blocks += len(keys)
                    stat_add("serving/tier_degraded", len(keys))
                    continue
                with self._lock:
                    for i, key in enumerate(keys):
                        self._put_locked(
                            key, host[:, i],
                            None if sca is None else sca[:, :, i])
                self._update_ledger()
                dt_ms = (time.perf_counter() - t0) * 1e3
                nbytes = len(keys) * self.entry_nbytes
                self._demo_ms.append(dt_ms)
                stat_add("serving/tier_demote", len(keys))
                stat_observe("serving/demotion_ms", dt_ms)
                stat_observe("serving/demotion_bytes", nbytes)
                _metrics.observe("serving_demotion_ms", dt_ms)
                _metrics.observe("serving_demotion_bytes", nbytes)
            finally:
                self._spill_q.task_done()

    # -- promotion (H2D) ---------------------------------------------------
    def request_promotion(
            self, keys: List[Tuple[int, ...]]) -> Optional[PromotionTicket]:
        """Coalesce the host-resident chain ``keys`` (root-first) into
        one promotion ticket. Idempotent per chain — a second request
        for the same chain returns the in-flight ticket. Returns None
        (degrade to a plain miss) when the tier is closed, the chain's
        root already left the store, or the promoter is busy past its
        double buffer."""
        if self._closed or not keys:
            return None
        keys = [tuple(k) for k in keys]
        with self._lock:
            tk = self._tickets.get(keys[-1])
            if tk is not None:
                return tk
            if keys[0] not in self._store:
                return None
            tk = PromotionTicket(keys)
            try:
                self._promo_q.put_nowait(tk)
            except queue.Full:
                self.promo_shed += 1
                stat_add("serving/tier_degraded")
                return None
            self._tickets[keys[-1]] = tk
            return tk

    def _promote_loop(self) -> None:
        while True:
            tk = self._promo_q.get()
            try:
                if tk is _END:
                    return
                try:
                    with self._lock:
                        entries, staged_keys = [], []
                        for key in tk.keys:
                            e = self._store.get(key)
                            if e is None:
                                break     # chain truncates at first gap
                            self._store.move_to_end(key)
                            entries.append(e)
                            staged_keys.append(key)
                    if not entries:
                        tk.failed = True
                        continue
                    # one contiguous batch per chain (redistribution
                    # paper: few big copies beat many small ones), and
                    # device_put is ASYNC — the H2D DMA overlaps the
                    # decode cycles running while the ticket waits
                    import jax
                    # pow2-pad the staged width (repeat the last block;
                    # adoption gathers only real lanes): every chain
                    # length then lands through one compiled
                    # gather/scatter shape per bucket instead of eagerly
                    # compiling a fresh pair on the scheduler thread
                    m = 1 << (len(entries) - 1).bit_length()
                    entries = entries + [entries[-1]] * (m - len(entries))
                    blocks = np.stack([e[0] for e in entries], axis=1)
                    tk.staged = jax.device_put(blocks)
                    if entries[0][1] is not None:
                        scales = np.stack([e[1] for e in entries], axis=2)
                        tk.staged_scales = jax.device_put(scales)
                    tk.staged_keys = staged_keys
                    tk.staged_at = time.perf_counter()
                except Exception:
                    tk.failed = True
            finally:
                if tk is not _END:
                    tk.ready.set()
                    self._progress.set()
                self._promo_q.task_done()

    def note_promoted(self, ticket: PromotionTicket, n_blocks: int) -> None:
        """Adoption callback (scheduler thread): the chain's blocks are
        device-resident and republished — close the latency ledger."""
        dt_ms = (time.perf_counter() - ticket.created_at) * 1e3
        nbytes = n_blocks * self.entry_nbytes
        self.promoted_blocks += n_blocks
        self._promo_ms.append(dt_ms)
        stat_add("serving/tier_promote", n_blocks)
        stat_observe("serving/promotion_ms", dt_ms)
        stat_observe("serving/promotion_bytes", nbytes)
        _metrics.observe("serving_promotion_ms", dt_ms)
        _metrics.observe("serving_promotion_bytes", nbytes)

    def ticket_done(self, ticket: PromotionTicket) -> None:
        """Retire a ticket from the registry (adopted or failed) so a
        later hit on the same chain can promote again."""
        with self._lock:
            for key, tk in list(self._tickets.items()):
                if tk is ticket:
                    del self._tickets[key]

    def wait_progress(self, timeout: float) -> bool:
        """Nap until SOME ticket completes (or ``timeout``): the
        scheduler's anti-hot-spin wait when the only queued requests
        are promotion-waiters and no decode slot is active. A host
        Event wait — never a device sync."""
        hit = self._progress.wait(timeout)
        self._progress.clear()
        return hit

    # -- lifecycle ---------------------------------------------------------
    def _update_ledger(self) -> None:
        _memory.ledger_set(f"{self.ledger_key}/in_use", self.bytes_in_use)

    def drain(self) -> None:
        """Block until every queued demotion and promotion has been
        processed — tests and the dry-run canary use this to make the
        async tier deterministic; the serving path never calls it."""
        self._spill_q.join()
        self._promo_q.join()

    def close(self) -> None:
        """Stop both worker threads (queued work drains first) and drop
        the ledger entries. Idempotent; the store itself survives so
        late ``get``s in teardown paths stay safe."""
        if self._closed:
            return
        self._closed = True
        self._spill_q.put(_END)
        self._promo_q.put(_END)
        self._spiller.join(timeout=10.0)
        self._promoter.join(timeout=10.0)
        with self._lock:
            for tk in self._tickets.values():
                tk.failed = True
                tk.ready.set()
            self._tickets.clear()
        self._progress.set()
        _drop_tier_ledger(self.ledger_key)

    def stats(self) -> dict:
        """Host-tier snapshot for ``engine.stats()['host_tier']``."""
        with self._lock:
            blocks = len(self._store)
        out = {
            "capacity_bytes": self.capacity_bytes,
            "bytes_in_use": blocks * self.entry_nbytes,
            "blocks": blocks,
            "capacity_blocks": self.capacity_blocks,
            "demoted_blocks": self.demoted_blocks,
            "promoted_blocks": self.promoted_blocks,
            "dropped_blocks": self.dropped_blocks,
            "tier_evictions": self.tier_evictions,
            "promo_shed": self.promo_shed,
        }
        for label, ring in (("promotion_ms", self._promo_ms),
                            ("demotion_ms", self._demo_ms)):
            vals = sorted(ring)
            out[label] = ({"count": len(vals),
                           "p50": _percentile(vals, 0.5),
                           "p95": _percentile(vals, 0.95)}
                          if vals else {"count": 0})
        return out

    def __repr__(self):
        return (f"<HostBlockPool {self.name} blocks={self.blocks}/"
                f"{self.capacity_blocks} demoted={self.demoted_blocks} "
                f"promoted={self.promoted_blocks}>")
