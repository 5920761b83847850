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
device, updated in place by the jitted decode step and the jitted block
writer here (both donate the arena), and read by the jitted block reader.
See DESIGN.md "Paged KV pool".
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.layers import KVCache, PagedKV, dtype_of
from ..trace import traced


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after the caller
    has evicted everything it is willing to evict."""


def _to_blocks(leaf, rows: int, start: int, nb: int, bs: int):
    """The (n, rows*nb, bs, KV, hd) block slab of positions ``[start, S)``
    of the first ``rows`` rows of a (n, B, S, KV, hd) prefill leaf, the
    partial last block zero-padded."""
    n, _, s = leaf.shape[:3]
    leaf = leaf[:, :rows, start:]
    pad = nb * bs - (s - start)
    if pad:
        leaf = jnp.pad(leaf, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return leaf.reshape(n, rows * nb, bs, *leaf.shape[3:])


def _put_slices(dst, src, ids):
    """Block ``i`` of ``src`` into block ``ids[i]`` of ``dst``, one
    dynamic-update-slice each: in place when a block is a contiguous run of
    the arena's memory."""
    def put(i, dst):
        return lax.dynamic_update_slice_in_dim(
            dst, lax.dynamic_slice_in_dim(src, i, 1, axis=1), ids[i], axis=1)

    return lax.fori_loop(0, ids.shape[0], put, dst)


def _put_lanes(dst, src, ids):
    """The same write when the block axis is innermost in memory (the TPU's
    compact layout of an arena whose head size is under a lane tile, as
    stablelm-1.6b's 64): a block is then one lane of every tile, so a write
    per block would touch the whole arena once for each block.  Instead one
    pass places every block: the stored bits, split into bytes (small
    integers that bf16 holds exactly), go through a one-hot (2m, NB) matmul
    weighted 1 and 256 that the MXU accumulates in f32 without rounding, and
    the reassembled bits replace the written lanes — the same bits as a
    copy, -0.0 and NaN included."""
    nbytes = dst.dtype.itemsize
    uint = jnp.dtype(f"uint{8 * nbytes}")
    onehot = ids[:, None] == jnp.arange(dst.shape[1])[None, :]
    # one relayout of the slab to the arena's lane order, before the byte
    # planes are cut from it (else each plane is relayouted on its own)
    src = lax.optimization_barrier(jnp.moveaxis(src, 1, -1))
    bits = lax.bitcast_convert_type(src, uint)
    placed = None
    for lo in range(0, nbytes, 2):      # 16 bits a matmul: sums < 2**24
        hi = min(lo + 2, nbytes)
        planes = jnp.concatenate(
            [(bits >> (8 * b)) & 0xFF for b in range(lo, hi)], axis=-1)
        weights = jnp.concatenate(
            [onehot * float(256 ** (b - lo)) for b in range(lo, hi)])
        part = jnp.dot(planes.astype(jnp.bfloat16),
                       weights.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        part = part.astype(uint) << (8 * lo)
        placed = part if placed is None else placed | part
    vals = lax.bitcast_convert_type(placed, dst.dtype)
    out = jnp.where(onehot.any(0), vals, jnp.moveaxis(dst, 1, -1))
    return jnp.moveaxis(out, -1, 1)


def _take_slices(src, ids):
    return jnp.take(src, ids, axis=1)


def _take_lanes(src, ids):
    """Blocks ``ids`` of an arena leaf whose block axis is innermost in
    memory, without the relayout copy of the whole leaf that a gather along
    that axis makes there: each byte of the stored bits goes through a
    one-hot (NB, m) matmul, exact as in :func:`_put_lanes`, reading the leaf
    once a byte and never writing it."""
    nbytes = src.dtype.itemsize
    uint = jnp.dtype(f"uint{8 * nbytes}")
    onehot = jnp.arange(src.shape[1])[:, None] == ids[None, :]
    bits = lax.bitcast_convert_type(jnp.moveaxis(src, 1, -1), uint)
    out = None
    for b in range(nbytes):
        plane = ((bits >> (8 * b)) & 0xFF).astype(jnp.bfloat16)
        part = jnp.dot(plane, onehot.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        part = part.astype(uint) << (8 * b)
        out = part if out is None else out | part
    return jnp.moveaxis(lax.bitcast_convert_type(out, src.dtype), -1, 1)


def _blocks_minor(leaf) -> bool:
    """Whether the block axis of an arena leaf is innermost in memory."""
    return leaf.format.layout.major_to_minor[-1] == 1


def _block_writer(lanes: Sequence[bool], shardings=None):
    """The pool's one write path, jitted with the arenas donated so the
    result aliases their buffers: a write never copies the arena.

    ``write_blocks(arenas, leaves, ids, start, nb)`` takes, per decoder
    stack, the prefill's (k, v) leaves (n, B, S, KV, hd), forms the block
    slab of positions ``[start, S)`` of the first ``len(ids) // nb`` rows
    (bucket-dummy rows beyond them are dropped) and writes slab block ``i``
    to arena block ``ids[i]``: by :func:`_put_lanes` for the stacks that
    ``lanes`` marks (block axis innermost in memory), else by
    :func:`_put_slices`.  Under a mesh the result is pinned to the arenas'
    canonical ``shardings``, which donation needs to alias."""
    def write_blocks(arenas, leaves, ids, start, nb):
        rows = ids.shape[0] // nb
        out = []
        for arena, kv, by_lane in zip(arenas, leaves, lanes):
            put = _put_lanes if by_lane else _put_slices
            bs = arena.k.shape[2]
            out.append(PagedKV(*(
                put(dst, _to_blocks(leaf, rows, start, nb, bs), ids)
                for dst, leaf in zip(arena, kv))))
        if shardings is not None:
            out = lax.with_sharding_constraint(out, shardings)
        return out

    return jax.jit(write_blocks, static_argnums=(3, 4), donate_argnums=0)


def _block_reader(lanes: Sequence[bool]):
    """The pool's one read path: ``read_blocks(arenas, ids)`` gives, per
    decoder stack, the (n, len(ids), bs, KV, hd) K/V slabs of blocks
    ``ids``, by :func:`_take_lanes` for the stacks that ``lanes`` marks."""
    def read_blocks(arenas, ids):
        return [PagedKV(*((_take_lanes if by_lane else _take_slices)(
                    leaf, ids) for leaf in arena))
                for arena, by_lane in zip(arenas, lanes)]

    return jax.jit(read_blocks)


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
        # slice on every device.  The block writer pins its result to the
        # same layout, so it and the donated decode step alias in place.
        self.arena_shardings = None
        if mesh is not None:
            from ..distributed.sharding import (ShardingPlan, arena_specs,
                                                named)
            self.arena_shardings = named(
                mesh, arena_specs(self.arenas, mesh, plan or ShardingPlan()))
            self.arenas = jax.device_put(self.arenas, self.arena_shardings)
        lanes = [_blocks_minor(a.k) for a in self.arenas]
        self._write_blocks = _block_writer(lanes, self.arena_shardings)
        self._read_blocks = _block_reader(lanes)
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
        # every call of the block writer (prefill writes and unstashes) and
        # the blocks it moved
        self.total_writes = 0
        self.total_written_blocks = 0

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
        slabs = self._read_blocks(self.arenas,
                                  np.asarray(list(ids), np.int32))
        stash = [(np.asarray(a.k), np.asarray(a.v)) for a in slabs]
        self.total_stashed += len(ids)
        return stash

    def unstash_blocks(self, stash: list, ids: Sequence[int]) -> None:
        """Write a stash back into ``ids`` (the resume half): the blocks
        need not be the ones stashed from — block contents are
        position-independent, the row's block TABLE carries the ordering —
        and a gather-out/scatter-back round trip is a copy of the stored
        bits, so a resumed row decodes bit-identically to one never
        suspended."""
        ids = list(ids)
        assert stash and all(k.shape[1] == len(ids) for k, _ in stash), (
            "stash block count must match the destination run")
        # a stash slab (n, m, bs, KV, hd) is one row of m*bs positions
        leaves = [[leaf.reshape(leaf.shape[0], 1, -1, *leaf.shape[3:])
                   for leaf in kv] for kv in stash]
        self._write(leaves, np.asarray(ids, np.int32), 0, len(ids))
        self.total_unstashed += len(ids)

    # ------------------------------------------------------ device arenas
    @traced("pool.write")
    def write(self, stack_caches, row_blocks: Sequence[Sequence[int]],
              start: int = 0) -> None:
        """Write prefill-computed KV into block runs: positions
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
        for cache in stack_caches:
            span = cache.k.shape[2] - start
            assert nb * bs >= span, f"run of {nb} blocks < {span} positions"
        ids = np.concatenate([np.asarray(b, np.int32) for b in row_blocks])
        self._write([(c.k, c.v) for c in stack_caches], ids, start, nb)

    def _write(self, leaves, ids: np.ndarray, start: int, nb: int) -> None:
        """Run the block writer over every stack; the donated arenas are
        replaced by its result, so no reference to them may outlive this."""
        assert len(set(ids.tolist())) == len(ids), (
            "a write's blocks must differ")
        self.arenas = self._write_blocks(self.arenas, leaves, ids, start, nb)
        self.total_writes += 1
        self.total_written_blocks += len(ids)

    def gather_stacked(self, block_ids: Sequence[int], length: int):
        """Materialize a block run as the dense per-stack cache pytree the
        chunked-prefill path consumes: a list of :class:`KVCache` with
        k/v (n, 1, length, KV, hd) and pos (n, length).  A gather is a copy
        of the stored bits, so downstream compute is bit-identical to
        holding the dense cache directly."""
        slabs = self._read_blocks(self.arenas,
                                  np.asarray(block_ids, np.int32))
        out = []
        for slab in slabs:                       # (n, nb, bs, kv, hd)
            n = slab.k.shape[0]

            def dense(g):
                g = g.reshape(n, 1, -1, *g.shape[3:])
                return g[:, :, :length]

            pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32),
                                   (n, length))
            out.append(KVCache(dense(slab.k), dense(slab.v), pos))
        return out
