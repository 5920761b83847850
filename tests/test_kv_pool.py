"""KVBlockPool allocator/refcount/arena unit tests (no model forwards) and
the paged decode-attention kernel oracle checks."""
import dataclasses
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.models import LM
from repro.models.layers import KVCache
from repro.serving import KVBlockPool, PoolExhausted
from repro.serving.kv_pool import _block_reader, _block_writer


@pytest.fixture()
def pool():
    lm = LM(get_reduced("llama3-8b"))
    return KVBlockPool(lm, num_blocks=17, block_size=8)


def test_alloc_free_roundtrip(pool):
    assert pool.free_blocks == 16            # block 0 reserved as dummy
    a = pool.alloc(5)
    assert len(a) == 5 and 0 not in a
    assert pool.blocks_in_use == 5 and pool.free_blocks == 11
    pool.decref(a)
    assert pool.blocks_in_use == 0 and pool.free_blocks == 16


def test_refcount_sharing(pool):
    run = pool.alloc(4)
    pool.incref(run)                         # a second owner (e.g. a row)
    pool.decref(run)                         # first owner drops
    assert pool.blocks_in_use == 4           # still held
    pool.decref(run)
    assert pool.blocks_in_use == 0


def test_exhaustion_raises_and_leaves_state_clean(pool):
    a = pool.alloc(10)
    with pytest.raises(PoolExhausted):
        pool.alloc(7)
    assert pool.free_blocks == 6             # failed alloc took nothing
    pool.decref(a)
    assert pool.free_blocks == 16


def test_lease_success_counts_and_returns(pool):
    ids = pool.lease(6)
    assert ids is not None and len(ids) == 6
    assert pool.total_leased == 6 and pool.lease_shortfalls == 0
    assert pool.blocks_in_use == 6
    pool.decref(ids)                             # a lease is a normal run
    assert pool.blocks_in_use == 0 and pool.free_blocks == 16


def test_lease_shortfall_takes_nothing_and_never_raises(pool):
    held = pool.alloc(12)
    got = pool.lease(7)                          # only 4 free
    assert got is None
    assert pool.lease_shortfalls == 1 and pool.total_leased == 0
    assert pool.free_blocks == 4                 # shortfall took nothing
    # the pool stays fully usable after a shortfall
    ok = pool.lease(4)
    assert ok is not None and pool.free_blocks == 0
    pool.decref(ok)
    pool.decref(held)
    assert pool.free_blocks == 16 and pool.blocks_in_use == 0


def test_lease_shortfalls_accumulate(pool):
    pool.alloc(16)
    for i in range(3):
        assert pool.lease(1) is None
    assert pool.lease_shortfalls == 3


def test_blocks_for(pool):
    assert pool.blocks_for(0) == 0
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(8) == 1
    assert pool.blocks_for(9) == 2


def test_peak_tracking(pool):
    a = pool.alloc(3)
    b = pool.alloc(5)
    pool.decref(a)
    pool.alloc(1)
    assert pool.peak_in_use == 8
    assert pool.blocks_in_use == 6


def test_write_gather_roundtrip(pool):
    """Prefill KV scattered into block runs gathers back bit-identically
    (gather is a copy — this is what makes pool-backed prefix entries
    transparent to the suffix-prefill path)."""
    lm = LM(get_reduced("llama3-8b"))
    cfg = lm.cfg
    rng = np.random.default_rng(0)
    n, b, s = cfg.pattern[0][1], 2, 21       # s deliberately un-aligned
    shape = (n, b, s, cfg.n_kv_heads, cfg.hd)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    caches = [KVCache(k, v, jnp.broadcast_to(jnp.arange(s), (n, s)))]
    rows = [pool.alloc(pool.blocks_for(s)) for _ in range(b)]
    pool.write(caches, rows)
    for r in range(b):
        got = pool.gather_stacked(rows[r], s)[0]
        assert (np.asarray(got.k[:, 0]) == np.asarray(k[:, r])).all()
        assert (np.asarray(got.v[:, 0]) == np.asarray(v[:, r])).all()
        assert got.k.shape == (n, 1, s, cfg.n_kv_heads, cfg.hd)


def _bits(a):
    return np.asarray(a).view(np.uint16)


@pytest.mark.parametrize("lanes", [False, True], ids=["slices", "lanes"])
@pytest.mark.parametrize("b,s,rows,start", [
    (2, 21, 2, 0),          # start 0, partial last block zero-padded
    (2, 29, 2, 16),         # block-aligned start > 0
    (4, 21, 3, 0),          # bucket-dummy row (B > rows) dropped
    (1, 21, 1, 0),          # 1 row
    (2, 24, 2, 8),          # 2 rows, full last block
    (4, 21, 4, 0),          # 4 rows
], ids=["partial_block", "aligned_start", "dummy_rows", "one_row",
        "two_rows", "four_rows"])
def test_write_matches_eager_scatter(pool, lanes, b, s, rows, start):
    """The pool's writer — both placements, whichever the arena's memory
    layout picks — stores exactly the bits of an eager ``.at[:, ids].set``
    of the zero-padded block slab, -0.0 and NaN included, and leaves every
    other block (dummy 0 too) as it was."""
    lm = LM(get_reduced("llama3-8b"))
    cfg = lm.cfg
    n, bs = cfg.pattern[0][1], pool.block_size
    rng = np.random.default_rng(7)
    shape = (n, b, s, cfg.n_kv_heads, cfg.hd)
    k = rng.standard_normal(shape).astype(np.float32)
    k.flat[::97] = -0.0
    k.flat[5::211] = np.nan
    k = jnp.asarray(k, jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    old = [jnp.asarray(rng.standard_normal(a.shape), a.dtype)
           for a in pool.arenas[0]]
    pool.arenas[0] = type(pool.arenas[0])(*old)
    nb = pool.blocks_for(s - start)
    ids = rng.permutation(np.arange(1, pool.num_blocks))[:rows * nb]
    row_blocks = [list(ids[r * nb:(r + 1) * nb]) for r in range(rows)]

    def to_blocks(leaf):                     # the eager path, by hand
        leaf = leaf[:, :rows, start:]
        leaf = jnp.pad(leaf, ((0, 0), (0, 0), (0, nb * bs - (s - start)),
                              (0, 0), (0, 0)))
        return leaf.reshape(n, rows * nb, bs, *leaf.shape[3:])

    want = [a.at[:, ids].set(to_blocks(x)) for a, x in zip(old, (k, v))]
    pool._write_blocks = _block_writer([lanes])
    pool.write([KVCache(k, v, jnp.broadcast_to(jnp.arange(s), (n, s)))],
               row_blocks, start=start)
    for got, exp in zip(pool.arenas[0], want):
        assert (_bits(got) == _bits(exp)).all()
    assert pool.total_writes == 1
    assert pool.total_written_blocks == rows * nb


@pytest.mark.parametrize("lanes", [False, True], ids=["slices", "lanes"])
def test_gather_and_stash_match_eager_take(pool, lanes):
    """The pool's reader — both placements — gives exactly the bits of an
    eager ``jnp.take`` of the blocks, -0.0 and NaN included, to the dense
    gather and to a stash alike."""
    rng = np.random.default_rng(11)
    old = []
    for a in pool.arenas[0]:
        x = rng.standard_normal(a.shape).astype(np.float32)
        x.flat[::89] = -0.0
        x.flat[3::173] = np.nan
        old.append(jnp.asarray(x, a.dtype))
    pool.arenas[0] = type(pool.arenas[0])(*old)
    pool._read_blocks = _block_reader([lanes])
    ids = [int(i) for i in rng.permutation(np.arange(pool.num_blocks))[:5]]
    length = 5 * pool.block_size - 3
    got = pool.gather_stacked(ids, length)[0]
    stash = pool.stash_blocks(ids)[0]
    for leaf, dense, stashed in zip(old, (got.k, got.v), stash):
        want = jnp.take(leaf, jnp.asarray(ids), axis=1)
        assert (_bits(stashed) == _bits(want)).all()
        want = want.reshape(leaf.shape[0], 1, -1, *leaf.shape[3:])
        assert (_bits(dense) == _bits(want[:, :, :length])).all()


def test_write_is_in_place():
    """The compiled writer aliases the donated arena to its result, and no
    instruction but the parameters, their loop plumbing and in-place
    dynamic-update-slices has the arena's shape: no copy of the arena.  A
    float32 pool, as the CPU widens bf16 updates to float32 around them."""
    lm = LM(dataclasses.replace(get_reduced("llama3-8b"), dtype="float32"))
    pool = KVBlockPool(lm, num_blocks=64, block_size=8)
    arena = pool.arenas[0].k
    n = arena.shape[0]
    k = jnp.zeros((n, 2, 21, *arena.shape[3:]), arena.dtype)
    compiled = pool._write_blocks.lower(
        pool.arenas, [(k, k)], np.arange(1, 7, dtype=np.int32), 0,
        3).compile()
    hlo = compiled.as_text()
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}, may-alias\), "
                     r"\{1\}: \(1, \{\}, may-alias\) \}", hlo)
    shape = f"f32[{','.join(map(str, arena.shape))}]"
    ops_ = re.findall(re.escape(shape) + r"\{[^}]*\} ([\w-]+)\(", hlo)
    assert set(ops_) <= {"parameter", "get-tuple-element",
                         "dynamic-update-slice", "fusion"}, ops_
    for root in re.findall(r"ROOT %\S+ = " + re.escape(shape)
                           + r"\{[^}]*\} ([\w-]+)\(", hlo):
        assert root in ("dynamic-update-slice", "tuple"), root
    assert compiled.memory_analysis().temp_size_in_bytes < arena.nbytes


def test_stash_unstash_roundtrip_is_bit_identical(pool):
    """The preemption round trip (suspend: gather blocks to a host stash;
    resume: scatter into a DIFFERENT run) is a copy of the stored bits —
    the property that makes a resumed decode row byte-identical."""
    lm = LM(get_reduced("llama3-8b"))
    cfg = lm.cfg
    rng = np.random.default_rng(1)
    src = pool.alloc(3)
    dst = pool.alloc(3)                      # disjoint ids on purpose
    assert not set(src) & set(dst)
    n = cfg.pattern[0][1]
    vals = jnp.asarray(rng.standard_normal(
        (n, 3, pool.block_size, cfg.n_kv_heads, cfg.hd)), jnp.bfloat16)
    a = pool.arenas[0]
    idx = jnp.asarray(np.asarray(src, np.int32))
    pool.arenas[0] = type(a)(k=a.k.at[:, idx].set(vals),
                             v=a.v.at[:, idx].set(-vals))
    stash = pool.stash_blocks(src)
    pool.decref(src)                         # source may die while stashed
    pool.unstash_blocks(stash, dst)
    didx = jnp.asarray(np.asarray(dst, np.int32))
    got = pool.arenas[0]
    assert (np.asarray(got.k[:, didx]) == np.asarray(vals)).all()
    assert (np.asarray(got.v[:, didx]) == np.asarray(-vals)).all()
    assert pool.total_stashed == pool.total_unstashed == 3
    with pytest.raises(AssertionError):      # size mismatch is refused
        pool.unstash_blocks(stash, dst[:2])


def test_freeable_counts_only_unshared(pool):
    run = pool.alloc(4)
    pool.incref(run[:2])                     # two blocks shared with an LRU
    assert pool.freeable(run) == 2
    pool.decref(run[:2])
    assert pool.freeable(run) == 4


def test_write_rejects_unaligned_start(pool):
    lm = LM(get_reduced("llama3-8b"))
    cfg = lm.cfg
    n = cfg.pattern[0][1]
    z = jnp.zeros((n, 1, 16, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    caches = [KVCache(z, z, jnp.broadcast_to(jnp.arange(16), (n, 16)))]
    with pytest.raises(AssertionError):
        pool.write(caches, [pool.alloc(1)], start=3)


# ------------------------------------------------------ paged decode kernel
def _paged_case(seed, b, h, kvh, hd, bs, nb, maxb):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.bfloat16)
    # distinct non-dummy blocks per row, 0-padded tables
    ids = rng.permutation(np.arange(1, nb))[: b * maxb].reshape(b, maxb)
    n_blk = rng.integers(1, maxb + 1, size=b)
    tables = np.where(np.arange(maxb)[None, :] < n_blk[:, None], ids, 0)
    ctx = (n_blk - 1) * bs + rng.integers(1, bs + 1, size=b)
    return q, kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(ctx, jnp.int32)


@pytest.mark.parametrize("b,h,kvh,hd,bs,nb,maxb", [
    (2, 4, 2, 16, 8, 9, 2),
    (3, 8, 2, 32, 16, 13, 3),
    (1, 4, 4, 16, 8, 5, 4),
])
def test_paged_kernel_matches_ref(b, h, kvh, hd, bs, nb, maxb):
    q, kp, vp, tables, ctx = _paged_case(0, b, h, kvh, hd, bs, nb, maxb)
    r = ref.paged_decode_attention_ref(q, kp, vp, tables, ctx)
    k = ops.paged_decode_attention(q, kp, vp, tables, ctx)
    np.testing.assert_allclose(np.asarray(r, np.float32),
                               np.asarray(k, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_paged_ref_equals_dense_decode_ref():
    """Gathering a block run into a dense cache and masking by position is
    BIT-identical to the dense flash-decode oracle over that cache — the
    paged pool changes memory layout, not math."""
    b, h, kvh, hd, bs, nb, maxb = 3, 4, 2, 16, 8, 12, 3
    q, kp, vp, tables, ctx = _paged_case(1, b, h, kvh, hd, bs, nb, maxb)
    r = ref.paged_decode_attention_ref(q, kp, vp, tables, ctx)
    for i in range(b):
        kg = jnp.take(kp, tables[i], axis=0).reshape(maxb * bs, kvh, hd)
        vg = jnp.take(vp, tables[i], axis=0).reshape(maxb * bs, kvh, hd)
        pos = np.where(np.arange(maxb * bs) < int(ctx[i]),
                       np.arange(maxb * bs), -1).astype(np.int32)
        d = ref.decode_attention_ref(q[i:i + 1], kg[None], vg[None],
                                     jnp.asarray(pos))
        assert (np.asarray(d) == np.asarray(r[i:i + 1])).all()
