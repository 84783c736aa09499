"""Attention layers: GQA/MQA (+ local windows, softcap, qk-norm) and MLA.

Each layer exposes:
  init(rng, cfg)                  -> (params, axes)
  fwd(params, x, cfg, layer_meta) -> y                  (training/prefill)
  fwd_kv(...)                     -> y, (k, v)          (prefill: KV out)
  decode(params, x, cache slices) -> y, new kv          (one token, paged)

Decode reads the paged pool through the reference gather path; the
Pallas ``paged_attention`` kernel implements the same contract (tests
assert equality).  MLA decode uses the
**absorbed** form: only the compressed latent stream (kv_lora + rope) is
cached -- the paper's block-quantum argument taken to its logical end.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ref as kref
from repro.launch import shardings as SH
from repro.models.common import (AxTree, Params, apply_rope, dense_init,
                                 flash_attention, head_rmsnorm)

_NEG = -1e30


# ===================== GQA =====================
def init_gqa(rng, cfg: ModelConfig) -> Tuple[Params, AxTree]:
    d, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.hd
    r = jax.random.split(rng, 4)
    p = {"wq": dense_init(r[0], d, H * hd, cfg.jdtype),
         "wk": dense_init(r[1], d, KVH * hd, cfg.jdtype),
         "wv": dense_init(r[2], d, KVH * hd, cfg.jdtype),
         "wo": dense_init(r[3], H * hd, d, cfg.jdtype)}
    ax = AxTree(wq=("embed", "attn_heads"), wk=("embed", "attn_heads"),
                wv=("embed", "attn_heads"), wo=("attn_heads", "embed"))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.jdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.jdtype)
        ax["q_norm"] = (None,)
        ax["k_norm"] = (None,)
    return p, ax


def _gqa_qkv(p: Params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
             rope_theta: Optional[float] = None):
    B, S, d = x.shape
    H, KVH, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KVH, hd)
    v = (x @ p["wv"]).reshape(B, S, KVH, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = head_rmsnorm(k, p["k_norm"], cfg.norm_eps)
    # static gate on cfg (traced per-layer theta allowed, e.g. gemma3's
    # dual local/global rope base)
    if cfg.rope_theta > 0:
        theta = rope_theta if rope_theta is not None else cfg.rope_theta
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def gqa_fwd_kv(p: Params, x: jax.Array, cfg: ModelConfig, *,
               window: Optional[int], positions: jax.Array,
               causal: bool = True, q_chunk: int = 1024,
               rope_theta=None):
    """Full-sequence attention; returns output and (k, v) for prefill."""
    q, k, v = _gqa_qkv(p, x, cfg, positions, rope_theta)
    B, S = x.shape[:2]
    if SH.use_ctx_parallel(cfg.num_heads):
        # context parallelism: query sequence over 'model', heads whole
        q = SH.constrain(q, "batch", "ctx", None, None)
        k = SH.constrain(k, "batch", None, None, None)
        v = SH.constrain(v, "batch", None, None, None)
        o = flash_attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.attn_softcap, scale=cfg.query_scale,
                            q_chunk=S)
        o = SH.constrain(o, "batch", "ctx", None, None)
    else:
        o = flash_attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.attn_softcap, scale=cfg.query_scale,
                            q_chunk=q_chunk)
    return o.reshape(B, S, -1) @ p["wo"], (k, v)


def gqa_fwd(p, x, cfg, *, window=None, positions=None, causal=True,
            q_chunk=1024):
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    y, _ = gqa_fwd_kv(p, x, cfg, window=window, positions=positions,
                      causal=causal, q_chunk=q_chunk)
    return y


def gqa_decode(p: Params, x: jax.Array, cfg: ModelConfig,
               k_pool: jax.Array, v_pool: jax.Array,
               block_tables: jax.Array, seq_lens: jax.Array, *,
               window: Optional[jax.Array] = None, rope_theta=None,
               dp_groups: int = 1):
    """One-token decode against the paged pool.

    x: (B, d) hidden of the new token.  k_pool/v_pool: (NB, BT, KVH, hd)
    this layer's slices.  Returns (y (B, d), (k_new, v_new)) -- the
    caller writes k_new/v_new into the pool at seq_lens (pre-advance).
    ``window``: None or traced scalar (0 => global) so local/global
    layers share one scanned body.
    """
    B, d = x.shape
    H, KVH, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    positions = seq_lens[:, None]                     # new token's position
    q, k, v = _gqa_qkv(p, x[:, None], cfg, positions, rope_theta)
    q = q[:, 0].reshape(B, KVH, H // KVH, hd)
    k_new, v_new = k[:, 0], v[:, 0]                   # (B, KVH, hd)
    # pin the decode-attention layout: kv-head sharded when divisible,
    # otherwise batch-only (replicated over 'model' -- decode attention
    # FLOPs are negligible, and an ambiguous layout makes GSPMD all-
    # gather the whole pool carry, see EXPERIMENTS.md §Perf cell B)
    tp = SH.tp_size()
    if tp > 1:
        # pin ONLY q and the output: pinning k_new/v_new too fights the
        # pool's propagated layout and makes XLA re-lay-out the whole
        # stacked pool accumulator every layer (measured 10x memory)
        ha = "heads" if KVH % tp == 0 else None
        q = SH.constrain(q, "batch", ha, None, None)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5

    # attention over cached tokens (the new token is merged below):
    o_c, l_c, m_c = _paged_ref(q, k_pool, v_pool, block_tables, seq_lens,
                               scale=scale, softcap=cfg.attn_softcap,
                               window=window, dp_groups=dp_groups)
    # new token attends to itself (always inside any window):
    s_self = jnp.einsum("bhgd,bhd->bhg", q.astype(jnp.float32) * scale,
                        k_new.astype(jnp.float32))
    if cfg.attn_softcap is not None:
        s_self = cfg.attn_softcap * jnp.tanh(s_self / cfg.attn_softcap)
    o = _merge_self(o_c, l_c, m_c, s_self,
                    v_new[:, :, None, :].astype(jnp.float32))
    if tp > 1:
        o = SH.constrain(o, "batch", "heads" if KVH % tp == 0 else None,
                         None, None)
    y = o.reshape(B, H * hd).astype(x.dtype) @ p["wo"]
    return y, (k_new, v_new)


def _scatter_span(pool_l, kv, write_tables, bt: int, dp_groups: int = 1):
    """Scatter a block-aligned token span into the pool.

    kv: (B, SQ, KVH, hd) with SQ % bt == 0; write_tables: (B, SQ // bt)
    physical block ids.  Aliased (COW-shared) and padding positions carry
    the sink block id: those writes land in the sink block and are never
    read back.  Group-batched when dp_groups > 1.
    """
    B, SQ = kv.shape[:2]
    nb = SQ // bt
    val = kv.astype(pool_l.dtype).reshape(B, nb, bt, *kv.shape[2:])
    if dp_groups <= 1:
        return pool_l.at[write_tables.reshape(B * nb)].set(
            val.reshape(B * nb, bt, *kv.shape[2:]))
    NBl = pool_l.shape[0] // dp_groups
    Bl = B // dp_groups
    pg = pool_l.reshape(dp_groups, NBl, *pool_l.shape[1:])
    out = jax.vmap(lambda pl, tb, vv: pl.at[tb].set(vv))(
        pg, write_tables.reshape(dp_groups, Bl * nb),
        val.reshape(dp_groups, Bl * nb, bt, *kv.shape[2:]))
    return out.reshape(pool_l.shape)


def gqa_prefill_paged(p: Params, x: jax.Array, cfg: ModelConfig,
                      k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, kv_lens: jax.Array,
                      q_starts: jax.Array, write_tables: jax.Array, *,
                      window: Optional[jax.Array] = None, rope_theta=None,
                      dp_groups: int = 1):
    """Suffix-only prefill against the paged pool (COW prefix sharing).

    x: (B, SQ, d) hiddens of the un-cached suffix; row b's token i sits
    at absolute position q_starts[b] + i.  The suffix's KV is scattered
    into the pool FIRST (through ``write_tables`` -- sink where the block
    is aliased from the parent, which already holds identical values),
    then every suffix query attends *through the block table* to the
    whole prefix+suffix with causal masking offset by the cached length.
    Prefix sharing thereby saves FLOPs, not just bytes.

    Returns (y (B, SQ, d), (k_pool, v_pool) updated).  On TPU the Pallas
    ``kernels.paged_prefill`` kernel implements the same contract (tests
    assert equality).
    """
    B, SQ, _ = x.shape
    H, KVH, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    bt = k_pool.shape[1]
    positions = q_starts[:, None] + jnp.arange(SQ)[None, :]
    q, k, v = _gqa_qkv(p, x, cfg, positions, rope_theta)
    k_pool = _scatter_span(k_pool, k, write_tables, bt, dp_groups)
    v_pool = _scatter_span(v_pool, v, write_tables, bt, dp_groups)
    qh = q.reshape(B, SQ, KVH, H // KVH, hd)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    o = _paged_prefill_ref(qh, k_pool, v_pool, block_tables, kv_lens,
                           positions, scale=scale, softcap=cfg.attn_softcap,
                           window=window, dp_groups=dp_groups)
    y = o.reshape(B, SQ, H * hd).astype(x.dtype) @ p["wo"]
    return y, (k_pool, v_pool)


def _paged_prefill_ref(q, k_pool, v_pool, block_tables, kv_lens, positions, *,
                       scale: float, softcap: Optional[float],
                       window: Optional[jax.Array],
                       v_dim: Optional[int] = None, dp_groups: int = 1):
    """Reference suffix-prefill attention through the block table.

    q: (B, SQ, KVH, G, Dk); positions: (B, SQ) absolute query positions.
    Same masking conventions as ``_paged_ref`` but causal per query row
    (kv <= q) with the window anchored at each query (kv > q - window,
    traced scalar, 0 => global).  Fully-masked rows return 0.
    """
    B, SQ, KVH, G, Dk = q.shape
    NB, BT = k_pool.shape[:2]
    MB = block_tables.shape[1]
    Dv = v_dim if v_dim is not None else v_pool.shape[-1]

    tbl = jnp.maximum(block_tables, 0)
    k = _grouped_gather(k_pool, tbl, dp_groups).reshape(B, MB * BT, KVH, -1)
    v = _grouped_gather(v_pool, tbl, dp_groups
                        ).reshape(B, MB * BT, KVH, -1)[..., :Dv]
    s = jnp.einsum("bqhgd,bshd->bhgqs", (q * scale).astype(k.dtype), k,
                   preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(MB * BT)[None, None, :]
    q_abs = positions[:, :, None]                     # (B, SQ, 1)
    valid = jnp.logical_and(kv_pos <= q_abs,
                            kv_pos < kv_lens[:, None, None])
    if window is not None:
        lo = jnp.where(window > 0, q_abs - window + 1, -1)
        valid &= kv_pos >= lo
    validb = valid[:, None, None, :, :]               # (B,1,1,SQ,S)
    s = jnp.where(validb, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - m) * validb
    l = jnp.sum(pr, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqs,bshd->bqhgd",
                   (pr / jnp.maximum(l, 1e-30)).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o


def _grouped_gather(pool, tbl, dp_groups: int):
    """pool (NB, BT, ...), tbl (B, MB) of group-LOCAL ids when dp_groups>1.

    The dp dimension is a *batch* dimension of the gather, so under GSPMD
    (pool blocks and batch co-sharded over the data axes) every shard
    gathers only from its own pool range -- no cross-device block motion.
    """
    if dp_groups <= 1:
        return pool[tbl]
    NB, B = pool.shape[0], tbl.shape[0]
    pg = pool.reshape(dp_groups, NB // dp_groups, *pool.shape[1:])
    tg = tbl.reshape(dp_groups, B // dp_groups, tbl.shape[1])
    out = jax.vmap(lambda pl, tb: pl[tb])(pg, tg)
    return out.reshape(B, tbl.shape[1], *pool.shape[1:])


def _merge_self(o_c, l_c, m_c, s_self, v_self):
    """Numerically-stable merge of cached-attention stats with the
    current token's score.  o_c: (B,KVH,G,Dv) normalized; l_c, m_c, s_self:
    (B,KVH,G); v_self: (B,KVH,1,Dv) broadcastable."""
    m_new = jnp.maximum(m_c, s_self)
    a_c = jnp.exp(m_c - m_new) * l_c                  # cached mass
    a_s = jnp.exp(s_self - m_new)                     # self mass
    denom = jnp.maximum(a_c + a_s, 1e-30)
    return (o_c * a_c[..., None] + v_self * a_s[..., None]) / denom[..., None]


def _paged_ref(q, k_pool, v_pool, block_tables, seq_lens, *,
               scale: float, softcap: Optional[float],
               window: Optional[jax.Array], v_dim: Optional[int] = None,
               dp_groups: int = 1):
    """Reference paged attention returning normalized output plus the
    softmax stats (l, m) so callers can merge the not-yet-written current
    token exactly.

    q: (B, KVH, G, Dk).  Returns (o (B,KVH,G,Dv), l (B,KVH,G), m (B,KVH,G)).
    Fully-masked rows (seq_len == 0) return l == 0, m == -1e30, o == 0.
    """
    B, KVH, G, Dk = q.shape
    Dv = v_dim if v_dim is not None else v_pool.shape[-1]

    tbl = jnp.maximum(block_tables, 0)
    # (B, MB, <block>) -> (B, MB * BT, KVH, Dk); a packed latent block
    # (BT / K rows of K tokens) flattens to the same token order
    k = _grouped_gather(k_pool, tbl, dp_groups).reshape(B, -1, KVH, Dk)
    S = k.shape[1]
    v = _grouped_gather(v_pool, tbl, dp_groups
                        ).reshape(B, S, KVH, -1)[..., :Dv]
    # bf16 operands + f32 accumulation (MXU-style): avoids materializing
    # f32 copies of the gathered KV views, the largest decode tensors
    s = jnp.einsum("bhgd,bshd->bhgs", (q * scale).astype(k.dtype), k,
                   preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(S)[None, :]
    valid = pos < seq_lens[:, None]
    if window is not None:
        lo = jnp.where(window > 0, seq_lens[:, None] - window + 1,
                       jnp.full_like(seq_lens, -1)[:, None])
        valid &= pos >= lo
    validb = valid[:, None, None, :]
    s = jnp.where(validb, s, _NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * validb            # masked rows -> 0
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32) / \
        jnp.maximum(l, 1e-30)[..., None]
    return o, l, m


# ===================== MLA =====================
def init_mla(rng, cfg: ModelConfig) -> Tuple[Params, AxTree]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    r = jax.random.split(rng, 8)
    p: Params = {}
    ax = AxTree()
    if m.q_lora_rank:
        p["wq_a"] = dense_init(r[0], d, m.q_lora_rank, cfg.jdtype)
        p["q_a_norm"] = jnp.ones((m.q_lora_rank,), cfg.jdtype)
        p["wq_b"] = dense_init(r[1], m.q_lora_rank, H * qk, cfg.jdtype)
        ax.update(wq_a=("embed", None), q_a_norm=(None,),
                  wq_b=(None, "attn_heads"))
    else:
        p["wq"] = dense_init(r[0], d, H * qk, cfg.jdtype)
        ax["wq"] = ("embed", "attn_heads")
    # joint compressed kv + shared rope key
    p["wkv_a"] = dense_init(r[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                            cfg.jdtype)
    p["kv_a_norm"] = jnp.ones((m.kv_lora_rank,), cfg.jdtype)
    p["wk_b"] = dense_init(r[3], m.kv_lora_rank, H * m.qk_nope_head_dim,
                           cfg.jdtype)
    p["wv_b"] = dense_init(r[4], m.kv_lora_rank, H * m.v_head_dim, cfg.jdtype)
    p["wo"] = dense_init(r[5], H * m.v_head_dim, d, cfg.jdtype)
    ax.update(wkv_a=("embed", None), kv_a_norm=(None,),
              wk_b=(None, "attn_heads"), wv_b=(None, "attn_heads"),
              wo=("attn_heads", "embed"))
    return p, ax


def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    B, S = x.shape[:2]
    H = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        from repro.models.common import rmsnorm
        qa = rmsnorm(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
        q = (qa @ p["wq_b"]).reshape(B, S, H, qk)
    else:
        q = (x @ p["wq"]).reshape(B, S, H, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, positions):
    """x -> (c_kv normalized (B,S,lora), k_rope (B,S,rope))."""
    m = cfg.mla
    from repro.models.common import rmsnorm
    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_fwd_kv(p: Params, x: jax.Array, cfg: ModelConfig, *,
               positions: jax.Array, q_chunk: int = 1024):
    """Training/prefill MLA (decompressed form). Returns y and the latent
    stream (c_kv || k_rope) for the paged cache."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ p["wv_b"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if SH.use_ctx_parallel(H):
        q = SH.constrain(q, "batch", "ctx", None, None)
        k = SH.constrain(k, "batch", None, None, None)
        v = SH.constrain(v, "batch", None, None, None)
        o = flash_attention(q, k, v, causal=True, softcap=cfg.attn_softcap,
                            scale=scale, q_chunk=S)
        o = SH.constrain(o, "batch", "ctx", None, None)
    else:
        o = flash_attention(q, k, v, causal=True, softcap=cfg.attn_softcap,
                            scale=scale, q_chunk=q_chunk)
    y = o.reshape(B, S, -1) @ p["wo"]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)  # (B, S, latent_dim)
    return y, latent


def mla_fwd(p, x, cfg, *, positions=None, q_chunk=1024, **_):
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    y, _ = mla_fwd_kv(p, x, cfg, positions=positions, q_chunk=q_chunk)
    return y


def mla_decode(p: Params, x: jax.Array, cfg: ModelConfig,
               c_pool: jax.Array, block_tables: jax.Array,
               seq_lens: jax.Array, dp_groups: int = 1, **_):
    """Absorbed-MLA decode over the latent paged pool.

    c_pool: (NB, BT / K, K * latent_dim), K tokens to a row (see
    ``PagedKVConfig.packed``), where latent = kv_lora || k_rope.
    Scores: q_nope^T W_kb^T c + q_rope^T k_rope  ==  q_eff . latent
    with q_eff = [W_kb^T q_nope, q_rope].  Output: (attn @ c) absorbed
    through W_vb then W_o.  Cache traffic per token: latent_dim values
    instead of H*(nope+v) -- 576 vs 4096 for deepseek-v2-lite.
    """
    m = cfg.mla
    B, _ = x.shape
    H = cfg.num_heads
    positions = seq_lens[:, None]
    q_nope, q_rope = _mla_q(p, x[:, None], cfg, positions)  # (B,1,H,*)
    # absorb W_kb: (B,H,lora)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    q_eff = jnp.concatenate([q_lat, q_rope[:, 0].astype(jnp.float32)],
                            axis=-1)[:, None]          # (B,1,H,latent)
    q_eff = q_eff.reshape(B, 1, H, m.latent_dim)

    c_new, k_rope_new = _mla_latent(p, x[:, None], cfg, positions)
    latent_new = jnp.concatenate([c_new, k_rope_new], axis=-1)[:, 0]  # (B,lat)

    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_pr = q_eff[:, 0][:, None]                       # (B, KVH=1, G=H, lat)
    o_c, l_c, m_c = _paged_ref(q_pr, c_pool, c_pool, block_tables, seq_lens,
                               scale=scale, softcap=None, window=None,
                               v_dim=m.kv_lora_rank, dp_groups=dp_groups)
    # merge the new token (self-attention term)
    s_self = jnp.einsum("bhd,bd->bh", q_eff[:, 0].astype(jnp.float32) * scale,
                        latent_new.astype(jnp.float32))[:, None]  # (B,1,H)
    c_self = latent_new[:, : m.kv_lora_rank].astype(jnp.float32)
    o = _merge_self(o_c, l_c, m_c, s_self,
                    c_self[:, None, None, :])          # (B,1,H,lora)
    o = o.reshape(B, H, m.kv_lora_rank)
    # un-absorb through W_vb then W_o
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o_v = jnp.einsum("bhl,lhv->bhv", o, wv_b.astype(jnp.float32))
    y = o_v.reshape(B, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return y, latent_new


def mla_decode_split(p: Params, x: jax.Array, cfg: ModelConfig,
                     c_pool: jax.Array, r_pool: jax.Array,
                     block_tables: jax.Array, seq_lens: jax.Array,
                     dp_groups: int = 1):
    """Latent-TP absorbed-MLA decode: the kv_lora stream (c_pool,
    (NB, BT, lora)) is shardable over 'model' on its last dim; the rope
    stream (r_pool, (NB, BT, rope)) stays replicated.  The score is the
    SUM of two contractions, so partitioning the lora contraction yields
    partial scores + one tiny psum (inserted by GSPMD).

    Returns (y, (c_new (B, lora), rope_new (B, rope))).
    """
    m = cfg.mla
    B, _ = x.shape
    H = cfg.num_heads
    positions = seq_lens[:, None]
    q_nope, q_rope = _mla_q(p, x[:, None], cfg, positions)   # (B,1,H,*)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))             # (B,H,lora)
    q_r = q_rope[:, 0].astype(jnp.float32)                   # (B,H,rope)
    c_new, rope_new = _mla_latent(p, x[:, None], cfg, positions)
    c_new, rope_new = c_new[:, 0], rope_new[:, 0]

    tbl = jnp.maximum(block_tables, 0)
    MB = tbl.shape[1]
    BT = c_pool.shape[1]
    k_lora = _grouped_gather(c_pool, tbl, dp_groups).reshape(
        B, MB * BT, m.kv_lora_rank)
    k_rope = _grouped_gather(r_pool, tbl, dp_groups).reshape(
        B, MB * BT, m.qk_rope_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = (jnp.einsum("bhl,bsl->bhs", q_lat * scale,
                    k_lora.astype(jnp.float32)) +
         jnp.einsum("bhr,bsr->bhs", q_r * scale,
                    k_rope.astype(jnp.float32)))
    pos = jnp.arange(MB * BT)[None, :]
    valid = (pos < seq_lens[:, None])[:, None, :]
    s = jnp.where(valid, s, _NEG)
    mx = jnp.max(s, axis=-1)
    pr = jnp.exp(s - mx[..., None]) * valid
    l = jnp.sum(pr, axis=-1)
    o = jnp.einsum("bhs,bsl->bhl", pr, k_lora.astype(jnp.float32)) / \
        jnp.maximum(l, 1e-30)[..., None]                     # (B,H,lora)
    # merge the new (unwritten) token
    s_self = (jnp.einsum("bhl,bl->bh", q_lat * scale,
                         c_new.astype(jnp.float32)) +
              jnp.einsum("bhr,br->bh", q_r * scale,
                         rope_new.astype(jnp.float32)))
    o = _merge_self(o[:, None], l[:, None], mx[:, None], s_self[:, None],
                    c_new.astype(jnp.float32)[:, None, None, :])[:, 0]
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o_v = jnp.einsum("bhl,lhv->bhv", o, wv_b.astype(jnp.float32))
    y = o_v.reshape(B, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return y, (c_new, rope_new)
