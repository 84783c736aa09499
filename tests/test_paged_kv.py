"""PagedKVCache semantics: append/prefill/gather, manager policies, swap."""

import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.blockpool import OutOfBlocksError
from repro.core.paged_kv import PagedKVCache, PagedKVConfig, PagedKVManager


def make(B=3, S=32, layers=2, kvh=2, hd=4, bt=8, arena=None):
    cfg = PagedKVConfig(num_layers=layers, kv_heads=kvh, head_dim=hd,
                        block_tokens=bt, num_blocks=B * S // bt + 4,
                        max_blocks_per_seq=S // bt, dtype=jnp.float32)
    cache = PagedKVCache.create(cfg, B)
    mgr = PagedKVManager(cfg, arena=arena)
    tables = []
    for sid in range(B):
        mgr.admit(sid, S)
        tables.append(mgr.device_table(sid))
    cache = dataclasses.replace(cache,
                                block_tables=jnp.asarray(np.stack(tables)))
    return cfg, cache, mgr


def test_append_then_gather_equals_dense(rng):
    cfg, cache, _ = make()
    L, B, T = cfg.num_layers, 3, 20
    ks = rng.randn(T, L, B, cfg.kv_heads, cfg.head_dim).astype(np.float32)
    vs = rng.randn(T, L, B, cfg.kv_heads, cfg.head_dim).astype(np.float32)
    for t in range(T):
        cache = cache.append_token(jnp.asarray(ks[t]), jnp.asarray(vs[t]))
    for l in range(L):
        k, v = cache.gather_layer(cache.k_pool[l], cache.v_pool[l])
        np.testing.assert_allclose(np.asarray(k)[:, :T],
                                   ks[:, l].transpose(1, 0, 2, 3), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v)[:, :T],
                                   vs[:, l].transpose(1, 0, 2, 3), rtol=1e-6)


def test_prefill_equals_appends(rng):
    cfg, cache1, _ = make()
    _, cache2, _ = make()
    L, B, T = cfg.num_layers, 3, 16   # block aligned
    k = rng.randn(L, B, T, cfg.kv_heads, cfg.head_dim).astype(np.float32)
    v = rng.randn(L, B, T, cfg.kv_heads, cfg.head_dim).astype(np.float32)
    cache1 = cache1.write_prefill(jnp.asarray(k), jnp.asarray(v),
                                  jnp.full((B,), T, jnp.int32))
    for t in range(T):
        cache2 = cache2.append_token(jnp.asarray(k[:, :, t]),
                                     jnp.asarray(v[:, :, t]))
    np.testing.assert_allclose(np.asarray(cache1.k_pool),
                               np.asarray(cache2.k_pool), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cache1.seq_lens),
                                  np.asarray(cache2.seq_lens))


@pytest.mark.parametrize("width, per_row", [(288, 4), (40, 8)])
def test_packed_latent_pool_prefill_append_gather(rng, width, per_row):
    """A single latent stream packs ``per_row`` tokens to a pool row
    (MiniCPM3's 288-wide latent four to 1,152 lanes; a width no divisor
    of the block brings to 128 lanes, the whole block in one row).  A
    prefill and appends land where the table says, group-local ids under
    two data-parallel groups land as pool-wide ids do, and the gather
    returns every token in order."""
    L, B, bt, T0, T = 2, 4, 8, 16, 20
    pools = []
    for dp in (1, 2):
        cfg = PagedKVConfig(num_layers=L, kv_heads=1, head_dim=width,
                            block_tokens=bt, num_blocks=16,
                            max_blocks_per_seq=4, latent=True,
                            dtype=jnp.float32, dp_groups=dp)
        assert cfg.packed and cfg.tokens_per_row == per_row
        assert cfg.pool_shape() == (L, 16, bt // per_row, per_row * width)
        ids = np.arange(16).reshape(B, 4)[:, ::-1].copy()  # pool-wide
        if dp == 2:
            ids[2:] -= 8                                    # group-local
        cache = dataclasses.replace(PagedKVCache.create(cfg, B),
                                    block_tables=jnp.asarray(ids))
        lat = np.random.RandomState(7).randn(L, B, T, width).astype(
            np.float32)
        cache = cache.write_prefill(jnp.asarray(lat[:, :, :T0]), None,
                                    jnp.full((B,), T0, jnp.int32))
        for t in range(T0, T):
            cache = cache.append_token(jnp.asarray(lat[:, :, t]), None)
        pools.append(np.asarray(cache.k_pool))
        if dp == 1:
            for layer in range(L):
                got, _ = cache.gather_layer(cache.k_pool[layer], None)
                np.testing.assert_array_equal(np.asarray(got)[:, :T],
                                              lat[layer])
    np.testing.assert_array_equal(pools[0], pools[1])


def test_manager_admission_by_blocks():
    cfg = PagedKVConfig(num_layers=1, kv_heads=1, head_dim=4,
                        block_tokens=8, num_blocks=4, max_blocks_per_seq=4)
    mgr = PagedKVManager(cfg)
    assert mgr.can_admit(32)           # exactly 4 blocks
    mgr.admit(0, 24)                   # 3 blocks
    assert mgr.can_admit(8) and not mgr.can_admit(16)
    with pytest.raises(OutOfBlocksError):
        mgr.admit(1, 17)               # needs 3 blocks, 1 free
    mgr.release(0)
    assert mgr.can_admit(32)


def test_swap_out_in_relocates(rng):
    """Swap-in may land on different physical blocks; tables absorb it.

    The payload rides the Arena's transfer plane (migrate enqueues the
    d2h/h2d plans, the registered executor moves ONLY the sequence's
    blocks -- never the whole pool); the serve-layer store is the byte
    ledger over completed plans."""
    from repro.mem import Arena
    from repro.serve.swap import HostBlockStore
    arena = Arena()
    cfg, cache, mgr = make(B=2, S=16, arena=arena)
    k_np = rng.randn(*cache.k_pool.shape).astype(np.float32)
    cell = {"cache": dataclasses.replace(cache, k_pool=jnp.asarray(k_np))}
    arena.transfers.register_executor(
        mgr.pool_class,
        lambda: [cell["cache"].k_pool, cell["cache"].v_pool],
        lambda s: cell.update(cache=dataclasses.replace(
            cell["cache"], k_pool=s[0], v_pool=s[1])))
    store = HostBlockStore(arena, mgr.pool_class)
    blocks_before = list(mgr.tables[0])
    mgr.swap_out(0)
    arena.transfers.drain()
    assert 0 not in mgr.tables and mgr.swapped[0] == len(blocks_before)
    # occupy some freed blocks so swap-in must relocate
    mgr.admit(99, 8)
    new_ids = mgr.swap_in(0)
    assert new_ids != blocks_before
    arena.transfers.drain()
    np.testing.assert_array_equal(
        np.asarray(cell["cache"].k_pool)[:, np.asarray(new_ids)],
        k_np[:, np.asarray(blocks_before)])
    # transfer cost: blocks held, never pool size
    assert store.stats.swap_out_bytes == \
        len(blocks_before) * cfg.swap_nbytes_per_block()
    assert store.stats.swap_in_bytes == store.stats.swap_out_bytes


def test_cow_fork_shares_blocks():
    cfg, cache, mgr = make(B=2, S=32)
    used_before = mgr.allocator.num_used
    mgr.fork(0, 7, shared_tokens=16)   # 2 full blocks shared
    assert mgr.allocator.num_used == used_before  # no new blocks
    assert mgr.tables[7] == mgr.tables[0][:2]
    mgr.release(7)                      # refcount drop, parent intact
    assert all(mgr.allocator.is_allocated(b) for b in mgr.tables[0])


def test_cow_fork_shared_tail_write_barrier():
    """fork() aliases a partially-filled tail block; the first write into
    it (either party) triggers fork_for_write via ensure_writable."""
    cfg, cache, mgr = make(B=2, S=32)          # bt=8
    parent = list(mgr.tables[0])
    mgr.fork(0, 7, shared_tokens=12)           # block 1 only partially full
    assert mgr.tables[7] == parent[:2]
    assert mgr.allocator.refcount(parent[1]) == 2
    # write at pos 12 (inside shared tail) -> private copy for the child
    plan = mgr.ensure_writable(7, token_pos=12)
    assert plan is not None
    src, dst = plan
    assert src == parent[1] and dst != src
    assert mgr.tables[7][1] == dst and mgr.tables[0][1] == src
    assert mgr.allocator.refcount(src) == 1
    assert mgr.allocator.refcount(dst) == 1
    # parent now owns its tail exclusively: no further copy
    assert mgr.ensure_writable(0, token_pos=12) is None


def test_dp_grouped_semantics(rng):
    """dp_groups>1 with group-local ids == dp_groups=1 with global ids."""
    from repro.models.attention import _grouped_gather
    B, MB, NB, BT, K, H = 4, 2, 8, 4, 2, 3
    pool = jnp.asarray(rng.randn(NB, BT, K, H).astype(np.float32))
    # group-local tables: groups of 2 sequences, each group owns NB/2=4
    tbl_local = jnp.asarray(rng.randint(0, 4, (B, MB)).astype(np.int32))
    tbl_global = np.asarray(tbl_local).copy()
    tbl_global[2:] += 4
    out_dp = _grouped_gather(pool, tbl_local, 2)
    out_ref = pool[jnp.asarray(tbl_global)]
    np.testing.assert_array_equal(np.asarray(out_dp), np.asarray(out_ref))
