"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops


@pytest.mark.parametrize("nblocks,leaf", [(4, 128), (16, 256), (7, 512)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_tree_gather_sweep(nblocks, leaf, dtype, rng):
    leaves = jnp.asarray(rng.randn(nblocks, leaf).astype(dtype))
    table = jnp.asarray(rng.permutation(nblocks).astype(np.int32))
    out = ops.tree_gather(leaves, table, interpret=True)
    ref = ops.tree_gather_ref(leaves, table)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("nblocks,leaf", [(4, 128), (9, 1024)])
def test_tree_block_sum_sweep(nblocks, leaf, rng):
    leaves = jnp.asarray(rng.randn(nblocks, leaf).astype(np.float32))
    table = jnp.asarray(rng.permutation(nblocks)[: nblocks - 1].astype(np.int32))
    out = ops.tree_block_sum(leaves, table, interpret=True)
    ref = ops.tree_block_sum_ref(leaves, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("rows_per_block,width,n", [(8, 128, 17), (16, 64, 64)])
def test_tree_gather_rows_sweep(rows_per_block, width, n, rng):
    nb = 6
    pool = jnp.asarray(rng.randn(nb, rows_per_block, width).astype(np.float32))
    ltab = jnp.asarray(rng.permutation(nb).astype(np.int32))
    rows = jnp.asarray(rng.randint(0, nb * rows_per_block, n).astype(np.int32))
    out = ops.tree_gather_rows(pool, rows, ltab, rows_per_block,
                               interpret=True)
    ref = ops.tree_gather_rows_ref(pool, rows, ltab, rows_per_block)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("B,KVH,G,HD,BT,MB", [
    (2, 1, 8, 64, 16, 4),      # MQA
    (3, 2, 4, 128, 32, 3),     # GQA
    (1, 4, 1, 64, 8, 8),       # MHA-ish
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, KVH, G, HD, BT, MB, dtype, rng):
    NB = B * MB + 2
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(dtype))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    tables = jnp.asarray(rng.permutation(NB)[: B * MB].reshape(B, MB)
                         .astype(np.int32))
    lens = jnp.asarray(rng.randint(1, MB * BT + 1, B).astype(np.int32))
    out = ops.paged_attention(q, k_pool, v_pool, tables, lens,
                              interpret=True)
    ref = ops.paged_attention_ref(q, k_pool, v_pool, tables, lens)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 40), (50.0, 24)])
def test_paged_attention_softcap_window(softcap, window, rng):
    B, KVH, G, HD, BT, MB = 2, 2, 2, 64, 16, 5
    NB = B * MB
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(np.arange(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([61, 33], np.int32))
    out = ops.paged_attention(q, k_pool, v_pool, tables, lens,
                              softcap=softcap, window=window, interpret=True)
    ref = ops.paged_attention_ref(q, k_pool, v_pool, tables, lens,
                                  softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_paged_attention_mla_latent(rng):
    """Absorbed-MLA mode: values are the first v_dim lanes of the latent."""
    B, H, LAT, VD, BT, MB = 2, 8, 96, 64, 16, 4
    NB = B * MB
    q = jnp.asarray(rng.randn(B, 1, H, LAT).astype(np.float32))
    c_pool = jnp.asarray(rng.randn(NB, BT, 1, LAT).astype(np.float32))
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([50, 17], np.int32))
    out = ops.paged_attention(q, c_pool, c_pool, tables, lens, v_dim=VD,
                              interpret=True)
    ref = ops.paged_attention_ref(q, c_pool, c_pool, tables, lens, v_dim=VD)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_paged_attention_matches_model_decode_path(rng):
    """Kernel contract == the model's reference decode attention
    (_paged_ref + self-token merge)."""
    from repro.models.attention import _merge_self, _paged_ref
    B, KVH, G, HD, BT, MB = 2, 2, 3, 32, 8, 4
    NB = B * MB
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(np.arange(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([20, 9], np.int32))
    out = ops.paged_attention(q, k_pool, v_pool, tables, lens,
                              interpret=True)
    o, l, m = _paged_ref(q, k_pool, v_pool, tables, lens, scale=HD ** -0.5,
                         softcap=None, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("B,SQ,KVH,G,HD,BT,MB,QC", [
    (2, 16, 1, 8, 64, 16, 4, 8),    # MQA, chunked queries
    (3, 8, 2, 4, 128, 8, 3, 8),     # GQA, single chunk
    (1, 32, 4, 1, 64, 8, 8, 4),     # MHA-ish, deep sweep
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_paged_prefill_sweep(B, SQ, KVH, G, HD, BT, MB, QC, dtype, rng):
    NB = B * MB + 2
    q = jnp.asarray(rng.randn(B, SQ, KVH, G, HD).astype(dtype))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    tables = jnp.asarray(rng.permutation(NB)[: B * MB].reshape(B, MB)
                         .astype(np.int32))
    starts = jnp.asarray(rng.randint(0, MB * BT - SQ + 1, B).astype(np.int32))
    lens = starts + jnp.asarray(rng.randint(1, SQ + 1, B).astype(np.int32))
    out = ops.paged_prefill_attention(q, k_pool, v_pool, tables, lens,
                                      starts, q_chunk=QC, interpret=True)
    ref = ops.paged_prefill_attention_ref(q, k_pool, v_pool, tables, lens,
                                          starts)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 12), (50.0, 7)])
def test_paged_prefill_softcap_window(softcap, window, rng):
    B, SQ, KVH, G, HD, BT, MB = 2, 16, 2, 2, 64, 8, 5
    NB = B * MB
    q = jnp.asarray(rng.randn(B, SQ, KVH, G, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(np.arange(NB).reshape(B, MB).astype(np.int32))
    starts = jnp.asarray(np.array([17, 0], np.int32))
    lens = jnp.asarray(np.array([17 + 16, 9], np.int32))
    out = ops.paged_prefill_attention(q, k_pool, v_pool, tables, lens,
                                      starts, softcap=softcap, window=window,
                                      q_chunk=8, interpret=True)
    ref = ops.paged_prefill_attention_ref(q, k_pool, v_pool, tables, lens,
                                          starts, softcap=softcap,
                                          window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_paged_prefill_last_token_matches_decode_kernel(rng):
    """A 1-token suffix at position len-1 is exactly a decode step: the
    prefill kernel must agree with the decode kernel on it."""
    B, KVH, G, HD, BT, MB = 2, 2, 4, 64, 8, 4
    NB = B * MB
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([29, 13], np.int32))
    q = jnp.asarray(rng.randn(B, 1, KVH, G, HD).astype(np.float32))
    out = ops.paged_prefill_attention(q, k_pool, v_pool, tables, lens,
                                      lens - 1, interpret=True)
    dec = ops.paged_attention(q[:, 0], k_pool, v_pool, tables, lens,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(dec),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,KVH,G,HD,BT,MB", [
    (2, 1, 8, 64, 16, 4),      # MQA
    (3, 2, 4, 128, 32, 3),     # GQA
    (1, 4, 1, 64, 8, 8),       # MHA-ish
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_paged_attention_append_sweep(B, KVH, G, HD, BT, MB, dtype, rng):
    """Fused append-then-attend: the kernel writes the new token's K/V
    rows into the tail block (aliased in place) and attends over
    ``lens + 1`` in the same pass.  Pools must match the oracle's
    exactly -- the splice is a dtype-roundtrip write, every other row
    of the tail block is read and written back unchanged."""
    NB = B * MB + 2
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(dtype))
    k_new = jnp.asarray(rng.randn(B, KVH, HD).astype(dtype))
    v_new = jnp.asarray(rng.randn(B, KVH, HD).astype(dtype))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(dtype))
    # distinct blocks across rows: live tails are exclusively owned
    # (the engine's COW barrier guarantees this before every decode)
    tables = jnp.asarray(rng.permutation(NB)[: B * MB].reshape(B, MB)
                         .astype(np.int32))
    lens = jnp.asarray(rng.randint(0, MB * BT, B).astype(np.int32))
    # oracle first: the jitted fused step DONATES the pools
    ref_o, ref_k, ref_v = ops.paged_attention_append_ref(
        q, k_new, v_new, k_pool, v_pool, tables, lens)
    out, k_out, v_out = ops.paged_attention_append(
        q, k_new, v_new, k_pool, v_pool, tables, lens, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_o, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(k_out, np.float32),
                                  np.asarray(ref_k, np.float32))
    np.testing.assert_array_equal(np.asarray(v_out, np.float32),
                                  np.asarray(ref_v, np.float32))


@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 40), (50.0, 24)])
def test_paged_attention_append_softcap_window(softcap, window, rng):
    B, KVH, G, HD, BT, MB = 2, 2, 2, 64, 16, 5
    NB = B * MB
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(np.float32))
    k_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    v_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(np.arange(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([61, 33], np.int32))
    ref_o, ref_k, ref_v = ops.paged_attention_append_ref(
        q, k_new, v_new, k_pool, v_pool, tables, lens,
        softcap=softcap, window=window)
    out, k_out, v_out = ops.paged_attention_append(
        q, k_new, v_new, k_pool, v_pool, tables, lens,
        softcap=softcap, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(ref_v))


def test_paged_attention_append_edges(rng):
    """lens == 0 writes position 0 of the first block; lens == MB * BT
    (full table) drops the write and attends the whole table -- both
    must match the oracle's ``mode=\"drop\"`` discipline."""
    B, KVH, G, HD, BT, MB = 2, 2, 2, 64, 8, 3
    NB = B * MB
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(np.float32))
    k_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    v_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([0, MB * BT], np.int32))
    k_before = np.asarray(k_pool).copy()
    ref_o, ref_k, ref_v = ops.paged_attention_append_ref(
        q, k_new, v_new, k_pool, v_pool, tables, lens)
    out, k_out, v_out = ops.paged_attention_append(
        q, k_new, v_new, k_pool, v_pool, tables, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(ref_v))
    # row 0: the new K row really landed at block tables[0, 0], offset 0
    np.testing.assert_allclose(
        np.asarray(k_out)[int(tables[0, 0]), 0],
        np.asarray(k_new)[0], rtol=0, atol=0)
    # row 1 (full): pools untouched anywhere row 1's table points
    for j in range(MB):
        np.testing.assert_array_equal(
            np.asarray(k_out)[int(tables[1, j])],
            k_before[int(tables[1, j])])


def test_paged_attention_append_matches_write_then_attend(rng):
    """The fused step == scatter the rows yourself, then run the plain
    decode kernel over ``lens + 1`` (the eager path's two dispatches)."""
    B, KVH, G, HD, BT, MB = 2, 2, 4, 64, 8, 4
    NB = B * MB
    q = jnp.asarray(rng.randn(B, KVH, G, HD).astype(np.float32))
    k_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    v_new = jnp.asarray(rng.randn(B, KVH, HD).astype(np.float32))
    k_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(NB, BT, KVH, HD).astype(np.float32))
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.array([19, 7], np.int32))
    jt = np.asarray(lens) // BT
    phys = np.asarray(tables)[np.arange(B), jt]
    off = np.asarray(lens) - jt * BT
    k_ref = np.asarray(k_pool).copy()
    v_ref = np.asarray(v_pool).copy()
    k_ref[phys, off] = np.asarray(k_new)
    v_ref[phys, off] = np.asarray(v_new)
    out, k_out, v_out = ops.paged_attention_append(
        q, k_new, v_new, k_pool, v_pool, tables, lens, interpret=True)
    dec = ops.paged_attention(q, jnp.asarray(k_ref), jnp.asarray(v_ref),
                              tables, lens + 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dec),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_out), k_ref)
    np.testing.assert_array_equal(np.asarray(v_out), v_ref)


@pytest.mark.parametrize("nb,blk", [(10, (4, 8)), (6, (16,)), (12, (2, 4, 8))])
def test_block_copy_plan(nb, blk, rng):
    """Device-side compaction/swap-in: apply a (src, dst) copy plan."""
    from repro.core.block_table import apply_compaction, compaction_plan
    from repro.kernels.block_copy import block_copy
    pool = jnp.asarray(rng.randn(nb, *blk).astype(np.float32))
    live = sorted(rng.permutation(nb)[: nb // 2].tolist())
    plan = compaction_plan(live)
    if not plan:
        return
    src = jnp.asarray(np.array([s for s, _ in plan], np.int32))
    dst = jnp.asarray(np.array([d for _, d in plan], np.int32))
    out = block_copy(pool, src, dst, interpret=True)
    ref = np.asarray(pool).copy()
    for s, d in plan:
        ref[d] = np.asarray(pool)[s]
    np.testing.assert_array_equal(np.asarray(out), ref)
    # tables rewritten to the dense prefix address the same contents
    tables = {0: list(live)}
    apply_compaction(tables, plan)
    for old, new in zip(live, tables[0]):
        np.testing.assert_array_equal(ref[new], np.asarray(pool)[old])


@pytest.mark.parametrize("layers,nb,blk", [(1, 8, (4, 2, 5)), (3, 10, (8,)),
                                           (2, 6, (4, 3))])
def test_gather_blocks_compact(layers, nb, blk, rng):
    """Swap-out gather: output is COMPACT (L, n, *blk) -- bytes scale
    with the id list, never the pool."""
    from repro.kernels.block_copy import gather_blocks
    pool = jnp.asarray(rng.randn(layers, nb, *blk).astype(np.float32))
    ids = rng.permutation(nb)[: nb // 2].astype(np.int32)
    out = gather_blocks(pool, jnp.asarray(ids), interpret=True)
    assert out.shape == (layers, len(ids), *blk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pool)[:, ids])


@pytest.mark.parametrize("layers,nb,blk", [(2, 8, (4, 2, 5)), (1, 6, (8,))])
def test_copy_pool_blocks_plan(layers, nb, blk, rng):
    """COW fulfilment: a (src, dst) plan applied across the layer axis."""
    from repro.kernels.block_copy import copy_pool_blocks
    pool = jnp.asarray(rng.randn(layers, nb, *blk).astype(np.float32))
    src = np.array([1, 4, 2], np.int32)
    dst = np.array([5, 0, 3], np.int32)
    out = copy_pool_blocks(pool, jnp.asarray(src), jnp.asarray(dst),
                           interpret=True)
    ref = np.asarray(pool).copy()
    ref[:, dst] = np.asarray(pool)[:, src]
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("layers,nb,blk", [(2, 8, (4, 2, 5)), (1, 6, (8,)),
                                           (3, 5, (4, 3))])
def test_scatter_blocks_inverse_of_gather(layers, nb, blk, rng):
    """Swap-in scatter: payload[l, i] lands at pool[l, idx[i]], untouched
    blocks preserved; gathering the same ids returns the payload."""
    from repro.kernels.block_copy import gather_blocks, scatter_blocks
    pool = jnp.asarray(rng.randn(layers, nb, *blk).astype(np.float32))
    ids = np.array([3, 0, 2], np.int32)
    payload = jnp.asarray(rng.randn(layers, len(ids), *blk)
                          .astype(np.float32))
    out = scatter_blocks(pool, jnp.asarray(ids), payload, interpret=True)
    ref = np.asarray(pool).copy()
    ref[:, ids] = np.asarray(payload)
    np.testing.assert_array_equal(np.asarray(out), ref)
    back = gather_blocks(out, jnp.asarray(ids), interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(payload))


@pytest.mark.parametrize("nb,rows_per_block,start,n,rows", [
    (5, 32, 8, 16, [3, 0, 4]), (4, 24, 0, 24, [2, 2, 1]),
    (6, 40, 16, 8, [5, 1, 0, 3])])
def test_gather_scatter_rows_in_place(nb, rows_per_block, start, n, rows,
                                      rng):
    """The layered state decode's row DMAs: ``gather_rows`` reads
    ``pool[rows, start:start + n]``; ``scatter_rows`` writes it back in
    place and leaves every other element of the pool as it was; on the
    interpreter a repeated row takes the last piece."""
    from repro.kernels.block_copy import gather_rows, scatter_rows
    pool = jnp.asarray(rng.randn(nb, rows_per_block, 128).astype(np.float32))
    idx = jnp.asarray(np.array(rows, np.int32))
    got = gather_rows(pool, idx, jnp.int32(start), n, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(pool)[rows, start:start + n])
    upd = jnp.asarray(rng.randn(len(rows), n, 128).astype(np.float32))
    out = scatter_rows(pool, idx, jnp.int32(start), upd, interpret=True)
    ref = np.asarray(pool).copy()
    for i, r in enumerate(rows):
        ref[r, start:start + n] = np.asarray(upd)[i]
    np.testing.assert_array_equal(np.asarray(out), ref)
