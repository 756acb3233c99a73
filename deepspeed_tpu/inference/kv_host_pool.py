"""Host-memory KV tier behind the paged block allocator (tiered KV cache).

The prefix cache's cold LRU list preserves zero-ref blocks only until
allocation pressure reclaims them — reclaiming DESTROYS content a
returning user would re-hit, so at scale cache capacity (not compute)
bounds hit rate and TTFT. Host RAM is ~10x HBM: instead of destroying a
cold block, the allocator *demotes* it here — an async D2H copy of the
block's per-layer ``[L, bs, KV*Hd]`` k/v slices, keyed by the block's
blake2b hash chain (the same content address the device table uses) —
and a later admission whose prefix walks onto a demoted chain
*re-materializes* the block H2D into a freshly allocated device block
(``engine._ServeSession._run_fetches``) instead of recomputing its
prefill. The reference's ``swap_tensor`` / ZeRO-Infinity tiering applied
to serving.

Tier discipline (the conftest ``_no_kv_block_leaks`` fixture asserts it):

- a chain key lives in AT MOST ONE tier — a host entry is removed when
  its content is promoted back to a device block (fetch) and discarded
  when a device re-registration lands under the same key (recompute of
  identical content supersedes the host copy);
- the pool is bounded by ``max_blocks`` with its own LRU — a ``put``
  over capacity evicts the oldest entries (host eviction loses only a
  *cache* copy, never live state);
- entries are immutable once stored: content addressing means the bytes
  under a key can never change, so a host copy made at demotion time is
  valid forever (across serves, cache-off calls, even fresh device
  pools) until geometry/dtype changes rebuild the pool.

Async D2H: ``put`` stores the gathered device slices and kicks off
``copy_to_host_async`` — the demotion overlaps the running decode loop
the way the weight-streaming path overlaps layer H2D copies. A bounded
pending queue (``pending_limit``) materializes the oldest in-flight
copies to numpy so at most a few block-sized device buffers are ever
held by the tier; ``get`` materializes on demand.

Fault injection: every D2H/H2D byte movement consults
``utils.fault_injection.guarded_io`` under virtual paths
``kv_host_pool/spill`` and ``kv_host_pool/fetch``. An injected
``OSError`` degrades gracefully — a faulted ``put`` skips the spill
(today's destroy-on-reclaim), a faulted ``get`` drops the entry and
reports a miss (the admission recomputes the tail) — with a rate-limited
warning and the ``serving/kv_host_errors`` counter; the serving loop
never wedges. ``SimulatedCrash`` (process death) propagates by design.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils import fault_injection as _fi
from deepspeed_tpu.utils.logging import warn_once


class _HostBlock:
    """One demoted block: k/v slices ``[L, bs, KV*Hd]``. Until
    :meth:`materialize` runs they are the gather program's device arrays
    with an async host copy in flight; after, plain numpy."""

    __slots__ = ("k", "v", "nbytes", "pending")

    def __init__(self, k, v):
        self.k = k
        self.v = v
        self.nbytes = int(k.nbytes) + int(v.nbytes)
        self.pending = True

    def materialize(self) -> None:
        if not self.pending:
            return
        self.k = np.asarray(self.k)
        self.v = np.asarray(self.v)
        self.pending = False


class KvHostPool:
    """LRU-bounded host pool of demoted KV blocks, keyed by the
    allocator's content-address chain keys. Thread-safe (the always-on
    serving loop demotes from its own thread while telemetry snapshots
    read the gauges)."""

    def __init__(self, max_blocks: int, block_shape: Tuple[int, ...],
                 dtype: str, pending_limit: int = 4, telemetry=None):
        if max_blocks < 1:
            raise ValueError("max_blocks must be >= 1")
        if len(block_shape) != 3:
            raise ValueError("block_shape must be [L, bs, KV*Hd], got "
                             f"{block_shape}")
        self.max_blocks = int(max_blocks)
        self.block_shape = tuple(int(s) for s in block_shape)
        self.dtype = str(dtype)
        # in-flight D2H copies: at most pending_limit block-sized device
        # buffers held before the oldest is forced down to numpy
        self.pending_limit = max(int(pending_limit), 1)
        self.telemetry = telemetry
        self._lock = threading.RLock()
        self._entries: "OrderedDict[bytes, _HostBlock]" = OrderedDict()
        self._pending: deque = deque()           # keys awaiting materialize
        self._nbytes = 0
        # plain host counters, always on (tests and the fault-degradation
        # path read these even with the metrics registry disabled)
        self.stats = {"spills": 0, "fetches": 0, "evictions": 0, "errors": 0}

    # ------------------------------------------------------------------ #
    # capacity / introspection

    @property
    def num_blocks(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return self._nbytes

    def contains(self, key: bytes) -> bool:
        """Read-only probe (no LRU refresh) — the allocator's tiered
        match walk uses this so probing never reorders eviction."""
        with self._lock:
            return key in self._entries

    def keys(self) -> List[bytes]:
        with self._lock:
            return list(self._entries)

    def matches_geometry(self, block_shape, dtype) -> bool:
        """Entries are only valid for one ``[L, bs, KV*Hd]`` + dtype —
        the engine rebuilds the pool when serving geometry changes."""
        return (self.block_shape == tuple(int(s) for s in block_shape)
                and self.dtype == str(dtype))

    # ------------------------------------------------------------------ #
    # tier transitions

    def _count_error(self, what: str, err: Exception) -> None:
        self.stats["errors"] += 1
        if self.telemetry is not None:
            self.telemetry.kv_host_errors.inc()
        warn_once(f"KV host pool {what} failed ({err}); degrading to "
                  "destroy-on-reclaim for the affected block(s) — serving "
                  "continues, the content will be recomputed on re-hit")

    def put(self, key: bytes, k_dev, v_dev) -> bool:
        """Demote one block: store the gathered device slices and start
        their async host copies. Returns True when a NEW entry was
        stored (the caller counts it as a spill); a duplicate key only
        refreshes LRU recency. Over-capacity puts evict the LRU tail.
        Injected I/O faults degrade to a no-op (destroy-on-reclaim)."""
        if tuple(k_dev.shape) != self.block_shape:
            raise ValueError(
                f"demoted slice shape {tuple(k_dev.shape)} does not match "
                f"the pool geometry {self.block_shape}")
        nbytes = int(k_dev.nbytes) + int(v_dev.nbytes)
        try:
            _fi.guarded_io("kv_host_pool/spill", nbytes)
        except OSError as e:                      # SimulatedCrash propagates
            self._count_error("spill (D2H)", e)
            return False
        # overlap with the serving loop: the copies ride the transfer
        # stream while the next fused step computes
        for a in (k_dev, v_dev):
            fn = getattr(a, "copy_to_host_async", None)
            if fn is not None:
                fn()
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return False
            ent = _HostBlock(k_dev, v_dev)
            self._entries[key] = ent
            self._nbytes += ent.nbytes
            self._pending.append(key)
            while len(self._pending) > self.pending_limit:
                old = self._entries.get(self._pending.popleft())
                if old is not None:
                    old.materialize()
            while len(self._entries) > self.max_blocks:
                _, dropped = self._entries.popitem(last=False)   # LRU
                self._nbytes -= dropped.nbytes
                self.stats["evictions"] += 1
        return True

    def get(self, key: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Materialized ``(k, v)`` for a host hit (LRU refreshed), or
        None on a miss. The entry STAYS in the pool — the scheduler calls
        :meth:`remove` only once the fetch actually lands on device, so a
        preemption between admission and fetch loses nothing. Injected
        faults drop the entry and report a miss (the admission recomputes
        that block's tail instead of wedging)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return None
            try:
                _fi.guarded_io("kv_host_pool/fetch", ent.nbytes)
                ent.materialize()
            except Exception as e:
                # injected OSError AND real failures (MemoryError on the
                # host copy, backend transfer errors) all degrade to a
                # miss — the admission recomputes the block; only
                # SimulatedCrash (BaseException) may propagate
                del self._entries[key]
                self._nbytes -= ent.nbytes
                self._count_error("fetch (H2D)", e)
                return None
            self._entries.move_to_end(key)
            self.stats["fetches"] += 1
            return ent.k, ent.v

    def remove(self, key: bytes) -> bool:
        """Drop an entry (content promoted back to a device block — a
        chain key lives in at most one tier). No-op on a miss."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is None:
                return False
            self._nbytes -= ent.nbytes
            return True

    discard = remove   # device re-registration superseding the host copy

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pending.clear()
            self._nbytes = 0

    def drain(self) -> None:
        """Force every in-flight D2H copy down to numpy (test/shutdown
        barrier; steady state bounds itself via ``pending_limit``)."""
        with self._lock:
            for ent in self._entries.values():
                ent.materialize()
            self._pending.clear()

    # ------------------------------------------------------------------ #
    # invariants (the conftest fixture's host-side assertions)

    def consistency_report(self) -> List[str]:
        """Internal-invariant violations (empty = consistent): the LRU is
        within its bound, byte accounting matches the entries, and every
        entry carries the pool geometry."""
        probs: List[str] = []
        with self._lock:
            if len(self._entries) > self.max_blocks:
                probs.append(
                    f"host pool holds {len(self._entries)} blocks over its "
                    f"bound of {self.max_blocks}")
            total = sum(e.nbytes for e in self._entries.values())
            if total != self._nbytes:
                probs.append(
                    f"host pool byte accounting drifted: tracked "
                    f"{self._nbytes}, actual {total}")
            for key, ent in self._entries.items():
                shape = tuple(getattr(ent.k, "shape", ()))
                if shape != self.block_shape:
                    probs.append(
                        f"host entry {key.hex()[:12]} has slice shape "
                        f"{shape}, pool geometry {self.block_shape}")
        return probs
