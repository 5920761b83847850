"""Block-paged KV pool: the ONE memory scheme behind serving.

A fixed arena of per-layer KV blocks (one :class:`~..models.layers.PagedKV`
per decoder stack, leaves (n_layers, num_blocks, block_size, KV, hd)) with a
host-side free-list allocator, per-sequence block tables, and ref-counted
block sharing.  Two previously unrelated memory schemes ride it:

 * **prefix-cache entries** (engine LRU) hold their region KV as a *pinned
   block run* — probe window jobs gather the run into the dense view the
   suffix-only prefill consumes, and decode sequences whose prompt shares
   the prefix incref the run's full blocks and append private blocks after
   it instead of re-materializing the prefix;
 * **decode sequences** (continuous-batching rows) own an ordered run of
   blocks covering positions ``[0, class + budget)``; a finished row frees
   its private blocks *immediately* (decref — shared prefix blocks survive
   while the LRU or other rows still hold them), so vacated memory admits
   queued requests between decode steps.

Block 0 is a permanent dummy: padded block-table slots and bucket-dummy
rows point (and may write) there, and it is never allocated, so its garbage
is only ever read through a NEG_INF mask.  Allocation/refcounts are plain
Python/numpy (the scheduler is host-side anyway); only the arenas live on
device, updated functionally by the jitted decode step and the eager
scatter/gather helpers here.  See DESIGN.md "Paged KV pool".
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from ..models.layers import KVCache, PagedKV, dtype_of
from ..trace import traced


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after the caller
    has evicted everything it is willing to evict."""


class KVBlockPool:
    def __init__(self, lm, num_blocks: int, block_size: int = 16,
                 mesh=None, plan=None):
        cfg = lm.cfg
        assert num_blocks >= 2, "need at least one real block beyond dummy 0"
        assert all(kind == "attn" for kind, _ in cfg.pattern), (
            "the paged pool holds full-attention KV only")
        self.block_size = block_size
        self.num_blocks = num_blocks
        dt = dtype_of(cfg.dtype)
        kv, hd = cfg.n_kv_heads, cfg.hd
        self.arenas = [
            PagedKV(k=jnp.zeros((n, num_blocks, block_size, kv, hd), dt),
                    v=jnp.zeros((n, num_blocks, block_size, kv, hd), dt))
            for kind, n in cfg.pattern]
        # Serving mesh (ServeEngine(mesh=...)): arenas become NamedSharding'd
        # arrays in the FEATURE layout — kv-heads over `model`, block dim
        # replicated — so everything below this line (free list, refcounts,
        # stashes) is mesh-oblivious: a block id addresses the same arena
        # slice on every device.  ``_pin`` re-commits eager scatter/gather
        # results to the canonical layout (a no-op when already there).
        self.arena_shardings = None
        if mesh is not None:
            import jax
            from ..distributed.sharding import (ShardingPlan, arena_specs,
                                                named)
            self.arena_shardings = named(
                mesh, arena_specs(self.arenas, mesh, plan or ShardingPlan()))
            self.arenas = jax.device_put(self.arenas, self.arena_shardings)
        # LIFO free list, block 0 (dummy) excluded for good
        self._free = list(range(num_blocks - 1, 0, -1))
        self._ref = np.zeros(num_blocks, np.int64)
        self.peak_in_use = 0
        self.total_allocs = 0
        # probe-row leases (see ServeEngine._lease_probe_blocks): transient
        # single-submission holds that arbitrate the same budget as decode
        # rows; counted separately so capacity reports can split persistent
        # occupancy from probe traffic
        self.total_leased = 0
        self.lease_shortfalls = 0
        # preemption traffic (see ServeEngine.paged_suspend/paged_resume):
        # blocks copied out to host stashes and scattered back
        self.total_stashed = 0
        self.total_unstashed = 0

    def _pin(self, si: int, arena):
        """Re-commit an eagerly-updated arena to the canonical sharding.
        Eager scatter (`.at[ids].set`) lets XLA pick the result layout; a
        device_put to the known NamedSharding is a no-op when it already
        matches and a reshard otherwise, so the donated decode step always
        sees identically-laid-out input."""
        if self.arena_shardings is None:
            return arena
        import jax
        return jax.device_put(arena, self.arena_shardings[si])

    # ---------------------------------------------------------- allocator
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.block_size)

    def alloc(self, n: int) -> list[int]:
        """Allocate ``n`` blocks with refcount 1."""
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} blocks, {len(self._free)} free "
                f"(pool {self.num_blocks}, block_size {self.block_size})")
        ids = [self._free.pop() for _ in range(n)]
        self._ref[ids] = 1
        self.total_allocs += n
        self.peak_in_use = max(self.peak_in_use, self.blocks_in_use)
        return ids

    def lease(self, n: int) -> "list[int] | None":
        """Best-effort transient allocation: ``n`` blocks with refcount 1
        when the free list can host them, ``None`` otherwise (the caller
        proceeds with unpooled transient memory — a lease never raises and
        never evicts).  Released via :meth:`decref` like any run."""
        if n > len(self._free):
            self.lease_shortfalls += 1
            return None
        # ownership transfers to the lease holder, who decrefs the run
        ids = self.alloc(n)  # lint: disable=kv-pairing
        self.total_leased += n
        return ids

    def freeable(self, ids: Sequence[int]) -> int:
        """How many of ``ids`` would return to the free list on one decref
        (refcount 1 — not shared with an LRU entry or another row).  The
        preemption policy uses this to size victim sets honestly: suspending
        a row whose run is mostly shared prefix frees little."""
        return sum(1 for i in ids if self._ref[i] == 1)

    def incref(self, ids: Sequence[int]) -> None:
        for i in ids:
            assert self._ref[i] > 0, f"incref of free block {i}"
            self._ref[i] += 1

    def decref(self, ids: Sequence[int]) -> None:
        """Drop one reference per id; blocks reaching 0 return to the free
        list (this IS ``free`` — owners simply drop their reference)."""
        for i in ids:
            assert self._ref[i] > 0, f"decref of free block {i}"
            self._ref[i] -= 1
            if self._ref[i] == 0:
                self._free.append(int(i))

    # ------------------------------------------------- preemption stashes
    def stash_blocks(self, ids: Sequence[int]) -> list:
        """Copy the contents of ``ids`` to a host-side stash (the suspend
        half of decode-row preemption): per decoder stack, the (n, len(ids),
        block_size, KV, hd) K/V slabs as numpy arrays.  A stash is a plain
        value — it holds no pool references, so the caller decides when the
        source blocks are released."""
        idx = jnp.asarray(np.asarray(list(ids), np.int32))
        stash = [(np.asarray(jnp.take(a.k, idx, axis=1)),
                  np.asarray(jnp.take(a.v, idx, axis=1)))
                 for a in self.arenas]
        self.total_stashed += len(ids)
        return stash

    def unstash_blocks(self, stash: list, ids: Sequence[int]) -> None:
        """Scatter a stash back into ``ids`` (the resume half): the blocks
        need not be the ones stashed from — block contents are
        position-independent, the row's block TABLE carries the ordering —
        and a gather-out/scatter-back round trip is a copy of the stored
        bits, so a resumed row decodes bit-identically to one never
        suspended."""
        ids = list(ids)
        assert stash and all(k.shape[1] == len(ids) for k, _ in stash), (
            "stash block count must match the destination run")
        idx = jnp.asarray(np.asarray(ids, np.int32))
        for si, (k, v) in enumerate(stash):
            arena = self.arenas[si]
            self.arenas[si] = self._pin(si, PagedKV(
                k=arena.k.at[:, idx].set(jnp.asarray(k)),
                v=arena.v.at[:, idx].set(jnp.asarray(v))))
        self.total_unstashed += len(ids)

    # ------------------------------------------------------ device arenas
    @traced("pool.write")
    def write(self, stack_caches, row_blocks: Sequence[Sequence[int]],
              start: int = 0) -> None:
        """Scatter prefill-computed KV into block runs: positions
        ``[start, S)`` of row ``r`` of ``stack_caches`` (a per-stack list of
        stacked :class:`KVCache`, leaves (n, B, S, KV, hd)) land in
        ``row_blocks[r]`` in order.  ``start`` must be block-aligned (a row
        appending after shared prefix blocks starts at their boundary);
        trailing bucket-dummy rows of the prefill batch (B > len(row_blocks))
        are dropped.  The partial last block is zero-padded — readers mask by
        valid length, never by block occupancy."""
        if not row_blocks:
            return
        bs = self.block_size
        assert start % bs == 0, "write start must be block-aligned"
        nb = len(row_blocks[0])
        assert all(len(b) == nb for b in row_blocks), (
            "rows of one write must cover equal block counts")
        ids = jnp.asarray(np.concatenate(
            [np.asarray(b, np.int32) for b in row_blocks]))
        rows = len(row_blocks)
        for si, cache in enumerate(stack_caches):
            k, v = cache.k, cache.v                  # (n, B, S, kv, hd)
            n, _, s = k.shape[:3]
            span = s - start
            pad = nb * bs - span
            assert pad >= 0, f"run of {nb} blocks < {span} positions"

            def to_blocks(leaf):
                leaf = leaf[:, :rows, start:]
                if pad:
                    leaf = jnp.pad(leaf, ((0, 0), (0, 0), (0, pad),
                                          (0, 0), (0, 0)))
                return leaf.reshape(n, rows * nb, bs, *leaf.shape[3:])

            arena = self.arenas[si]
            self.arenas[si] = self._pin(si, PagedKV(
                k=arena.k.at[:, ids].set(to_blocks(k)),
                v=arena.v.at[:, ids].set(to_blocks(v))))

    def gather_stacked(self, block_ids: Sequence[int], length: int):
        """Materialize a block run as the dense per-stack cache pytree the
        chunked-prefill path consumes: a list of :class:`KVCache` with
        k/v (n, 1, length, KV, hd) and pos (n, length).  A gather is a copy
        of the stored bits, so downstream compute is bit-identical to
        holding the dense cache directly."""
        ids = jnp.asarray(np.asarray(block_ids, np.int32))
        out = []
        for arena in self.arenas:
            n = arena.k.shape[0]

            def dense(leaf):
                g = jnp.take(leaf, ids, axis=1)      # (n, nb, bs, kv, hd)
                g = g.reshape(n, 1, -1, *g.shape[3:])
                return g[:, :, :length]

            pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32),
                                   (n, length))
            out.append(KVCache(dense(arena.k), dense(arena.v), pos))
        return out
