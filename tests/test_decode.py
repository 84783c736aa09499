"""The strong serving test: prefill + paged decode reproduces the full
forward logits EXACTLY (position by position) for every family."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.paged_kv import PagedKVCache, PagedKVManager
from repro.models.api import build_model, make_concrete_batch

B, S, S0 = 2, 24, 16
ATOL, RTOL = 4e-3, 2e-2


def _tables(kv, B, S):
    mgr = PagedKVManager(kv.config)
    tb = []
    for sid in range(B):
        mgr.admit(sid, S)
        tb.append(mgr.device_table(sid))
    return dataclasses.replace(kv, block_tables=jnp.asarray(np.stack(tb)))


def _check_lm(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)
    logits, _, _ = m.forward(p, batch, q_chunk=8)
    cache = _tables(PagedKVCache.create(m.kv_config(max_seq=S, batch=B), B),
                    B, S)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S0]
    last, cache = m.prefill(p, pre, cache, jnp.full((B,), S0, jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, S0 - 1]),
                               atol=ATOL, rtol=RTOL)
    for t in range(S0, S):
        lg, cache = m.decode_step(p, batch["tokens"][:, t], cache)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, t]),
                                   atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("arch", [
    "gemma_2b",               # MQA + geglu + embed scale
    "qwen3_moe_30b_a3b",      # MoE + qk-norm
    "deepseek_v2_lite_16b",   # MLA latent cache + shared experts + dense L0
    "minicpm3_4b",            # MLA with q-lora
    "minicpm3_4b_published",  # + own head, muP multipliers
    "gemma2_27b",             # local/global + softcaps + post-norms
    "gemma3_27b",             # 5:1 local + dual rope theta
])
def test_decoder_lm_decode_matches_forward(arch):
    _check_lm(arch)


def test_rwkv_decode_matches_forward():
    cfg = get_config("rwkv6_7b").reduced()
    m = build_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)
    logits, _, _ = m.forward(p, batch)
    st = m.init_state(B)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S0]
    last, st = m.prefill(p, pre, st)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, S0 - 1]),
                               atol=ATOL, rtol=RTOL)
    for t in range(S0, S):
        lg, st = m.decode_step(p, batch["tokens"][:, t], st)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, t]),
                                   atol=ATOL, rtol=RTOL)


def test_zamba_decode_matches_forward():
    cfg = get_config("zamba2_2p7b").reduced()
    m = build_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)
    logits, _, _ = m.forward(p, batch)
    st = m.init_state(B, max_seq=S)
    st = dataclasses.replace(st, kv=_tables(st.kv, B, S))
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S0]
    last, st = m.prefill(p, pre, st, jnp.full((B,), S0, jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, S0 - 1]),
                               atol=ATOL, rtol=RTOL)
    for t in range(S0, S):
        lg, st = m.decode_step(p, batch["tokens"][:, t], st)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, t]),
                                   atol=ATOL, rtol=RTOL)


def test_whisper_decode_matches_forward():
    cfg = get_config("whisper_tiny").reduced()
    m = build_model(cfg, max_positions=S)
    p, _ = m.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)
    logits, _, _ = m.forward(p, batch)
    st = m.init_state(B, max_seq=S)
    st = dataclasses.replace(st, self_kv=_tables(st.self_kv, B, S))
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :S0]
    last, st = m.prefill(p, pre, st, jnp.full((B,), S0, jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, S0 - 1]),
                               atol=ATOL, rtol=RTOL)
    for t in range(S0, S):
        lg, st = m.decode_step(p, batch["tokens"][:, t], st)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, t]),
                                   atol=ATOL, rtol=RTOL)


def test_decode_with_fragmented_blocks():
    """Physical block placement must not change results (the paper's
    relocation claim): permute the pool blocks + tables, same logits."""
    cfg = get_config("gemma_2b").reduced()
    m = build_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)
    kvcfg = m.kv_config(max_seq=S, batch=B,
                        num_blocks=B * (S // cfg.kv_block_tokens) + 6)

    def run(perm_seed):
        cache = PagedKVCache.create(kvcfg, B)
        mgr = PagedKVManager(kvcfg)
        rng = np.random.RandomState(perm_seed)
        # emulate fragmentation: burn a few random allocations first
        burn = []
        for _ in range(rng.randint(0, 5)):
            burn.append(mgr.allocator.alloc())
        for b in burn:
            if rng.rand() < 0.5:
                mgr.allocator.free(b)
        tb = []
        for sid in range(B):
            mgr.admit(sid, S)
            tb.append(mgr.device_table(sid))
        cache = dataclasses.replace(cache,
                                    block_tables=jnp.asarray(np.stack(tb)))
        pre = dict(batch)
        pre["tokens"] = batch["tokens"][:, :S0]
        last, cache = m.prefill(p, pre, cache, jnp.full((B,), S0, jnp.int32))
        outs = [np.asarray(last)]
        for t in range(S0, S):
            lg, cache = m.decode_step(p, batch["tokens"][:, t], cache)
            outs.append(np.asarray(lg))
        return np.stack(outs)

    a, b = run(1), run(2)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# -- prefill longer than one query chunk, not a whole number of chunks ----
# The same float32 arithmetic per query row either way; only the matmuls'
# tiling over rows differs, which can reorder a sum: 1e-5.
CHUNK_TOL = 1e-5


@pytest.mark.parametrize("Sq", [1088, 1536])
def test_flash_attention_pads_a_partial_last_chunk(Sq):
    from repro.models.common import flash_attention
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, Sq, 4, 16), jnp.float32)   # GQA, G = 2
    k = jax.random.normal(kk, (1, Sq, 2, 16), jnp.float32)
    v = jax.random.normal(kv, (1, Sq, 2, 8), jnp.float32)
    chunked = flash_attention(q, k, v, q_chunk=1024)
    whole = flash_attention(q, k, v, q_chunk=Sq)
    assert chunked.shape == (1, Sq, 4, 8)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               atol=CHUNK_TOL, rtol=CHUNK_TOL)


def test_mla_paged_prefill_past_one_query_chunk():
    """A 1,083-token prompt padded to 1,088 (136 blocks of 8): the paged
    prefill's last logits match the full forward pass's, and decoding
    through the latent it wrote matches the forward pass's next
    positions.  Prefill and forward compute the same rows (prefill's
    query chunks are 1,024 and 64 rows, the forward's one of 1,088):
    CHUNK_TOL scaled to logits of order 10, 1e-4.  Decode is the
    absorbed form, a different order of operations: ATOL, RTOL."""
    cfg = get_config("minicpm3_4b").reduced()
    m = build_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    S, L = 1088, 1083
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, S), 1,
                              cfg.vocab_size, jnp.int32)
    logits, _, _ = m.forward(p, {"tokens": toks}, q_chunk=S)
    cache = _tables(PagedKVCache.create(m.kv_config(max_seq=S, batch=1), 1),
                    1, S)
    pre = jnp.where(jnp.arange(S)[None] < L, toks, 0)
    last, cache = m.prefill(p, {"tokens": pre}, cache,
                            jnp.full((1,), L, jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, L - 1]),
                               atol=1e-4, rtol=1e-4)
    for t in range(L, S):
        lg, cache = m.decode_step(p, toks[:, t], cache)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, t]),
                                   atol=ATOL, rtol=RTOL)
