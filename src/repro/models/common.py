"""Shared building blocks: init helpers, norms, RoPE, MLPs, flash attention.

Parameters are plain nested dicts of arrays; every initializer also
declares *logical sharding axes* (a parallel pytree of tuples) that
``repro.launch.shardings`` maps onto the physical mesh.  Layer stacks are
built stacked (leading L axis) and consumed by ``lax.scan`` so HLO size
and compile time are depth-independent.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

# -- logical axis names (mapped to mesh axes in launch/shardings.py) -------
#   "embed"  : d_model        -> replicated (or fsdp'd over data)
#   "heads"  : attention heads / d_ff / experts' hidden -> "model"
#   "vocab"  : vocabulary      -> "model"
#   "layers" : stacked layers  -> replicated (scan axis)
#   "expert" : expert index    -> replicated in baseline, "model" under EP


# A pytree of logical-axis tuples mirroring a params tree.  Plain dict:
# jax.tree_util does not traverse dict *subclasses*.
AxTree = dict


def _init(rng, shape, scale, dtype):
    return (scale * jax.random.truncated_normal(rng, -2.0, 2.0, shape,
                                                jnp.float32)).astype(dtype)


def dense_init(rng, d_in: int, d_out: int, dtype, *, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return _init(rng, (d_in, d_out), scale, dtype)


def jit_init(init_fn: Callable, rng) -> Tuple[Params, AxTree]:
    """``init_fn(rng) -> (params, axes)`` with the params built in ONE
    jit, so XLA writes each tensor in its final dtype.  Run eagerly,
    ``_init`` first materialises a whole stacked tensor in float32: 4 GB
    for MiniCPM3-4B's (62, 2560, 6400) MLP stack, beside 8 GB of bf16
    parameters on a 16 GB chip.  Same values as the eager init."""
    cell = {}

    def params_only(r):
        p, cell["ax"] = init_fn(r)
        return p

    params = jax.jit(params_only)(rng)
    return params, cell["ax"]


def stacked(init_fn: Callable, rng, num: int, *args, **kw):
    """vmap an initializer over a leading stack axis (layers)."""
    rngs = jax.random.split(rng, num)
    return jax.vmap(lambda r: init_fn(r, *args, **kw))(rngs)


# -- norms ------------------------------------------------------------------
def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
            *, gemma_style: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    out = x * (1.0 + w) if gemma_style else x * w
    return out.astype(dt)


def head_rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (qwen3/gemma3): x (..., H, hd), weight (hd,)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


# -- rotary embeddings ----------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd) or (..., H, hd) single-pos; positions (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(num: int, dim: int) -> jax.Array:
    """Whisper-style sinusoids."""
    inv = 1.0 / (10000 ** (np.arange(dim // 2) / max(1, dim // 2 - 1)))
    pos = np.arange(num)[:, None] * inv[None, :]
    return jnp.asarray(np.concatenate([np.sin(pos), np.cos(pos)], axis=1),
                       jnp.float32)


# -- MLPs -------------------------------------------------------------------
def init_mlp(rng, d_model: int, d_ff: int, dtype) -> Tuple[Params, AxTree]:
    r1, r2, r3 = jax.random.split(rng, 3)
    p = {"wi": dense_init(r1, d_model, d_ff, dtype),
         "wg": dense_init(r2, d_model, d_ff, dtype),
         "wo": dense_init(r3, d_ff, d_model, dtype)}
    ax = AxTree(wi=("embed", "heads"), wg=("embed", "heads"),
                wo=("heads", "embed"))
    return p, ax


def mlp(x: jax.Array, p: Params, kind: str = "swiglu") -> jax.Array:
    act = jax.nn.gelu if kind == "geglu" else jax.nn.silu
    h = act(x @ p["wg"]) * (x @ p["wi"])
    return h @ p["wo"]


def _loss_chunk(S: int, target: int = 512) -> int:
    """Largest divisor of S that is <= target."""
    c = min(S, target)
    while S % c:
        c -= 1
    return c


def chunked_lm_loss(x: jax.Array, head_w: jax.Array, targets: jax.Array, *,
                    final_softcap: Optional[float] = None,
                    chunk: int = 512) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without materializing (B, S, V) f32 logits.

    x: (B, S, d) FINAL-NORMED hidden; head_w: (d, V); targets: (B, S)
    with -1 = masked.  Scans over sequence chunks; each chunk's logits
    are rematerialized in the backward pass (jax.checkpoint), so peak
    memory holds one (B, chunk, V) slab instead of the full logits.
    Returns (nll_sum, token_count).
    """
    B, S, d = x.shape
    c = _loss_chunk(S, chunk)
    xc = x.reshape(B, S // c, c, d).swapaxes(0, 1)        # (nc, B, c, d)
    tc = targets.reshape(B, S // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        xx, tt = xs
        logits = (xx @ head_w).astype(jnp.float32)
        if final_softcap is not None:
            logits = final_softcap * jnp.tanh(logits / final_softcap)
        mask = (tt >= 0).astype(jnp.float32)
        tgt = jnp.maximum(tt, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        nll, cnt = carry
        return (nll + ((lse - gold) * mask).sum(), cnt + mask.sum()), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc))
    return nll, cnt


# -- exact blockwise (flash-style) attention for training/prefill ---------
_NEG = -1e30


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    q_chunk: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Exact attention, scanned over query chunks to bound memory.

    q: (B, Sq, H, Dk); k: (B, Sk, KVH, Dk); v: (B, Sk, KVH, Dv).
    GQA handled by reshaping q to (B, Sq, KVH, G, Dk).  ``q_offset`` is
    the absolute position of q[0] (prefill continuation).
    Memory: O(B * H * q_chunk * Sk) instead of O(B * H * Sq * Sk).
    A length that is not a whole number of chunks is padded with query
    rows past the end, whose outputs are dropped: every real row's mask
    depends only on its own position, so the padding changes none.
    """
    B, Sq, H, Dk = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    if scale is None:
        scale = Dk ** -0.5
    qc = min(q_chunk, Sq)
    nc = -(-Sq // qc)
    if nc * qc != Sq:
        q = jnp.pad(q, ((0, 0), (0, nc * qc - Sq), (0, 0), (0, 0)))

    qr = (q.reshape(B, nc, qc, KVH, G, Dk)
          .transpose(1, 0, 3, 4, 2, 5))              # (nc, B, KVH, G, qc, Dk)
    kT = k.transpose(0, 2, 3, 1)                     # (B, KVH, Dk, Sk)
    vT = v.transpose(0, 2, 1, 3)                     # (B, KVH, Sk, Dv)
    kpos = jnp.arange(Sk)

    def chunk_fn(ci, qch):
        # qch: (B, KVH, G, qc, Dk).  Operands stay in the model dtype
        # (bf16 for full configs) with f32 ACCUMULATION -- halves the
        # score-matmul input traffic, the dominant train-time memory term
        # (EXPERIMENTS.md §Perf), and is exact for f32 test configs.
        s = jnp.einsum("bhgqd,bhds->bhgqs", (qch * scale).astype(kT.dtype),
                       kT, preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_offset + ci * qc + jnp.arange(qc)
        valid = jnp.ones((qc, Sk), bool)
        if causal:
            valid &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            # traced-friendly: window <= 0 means "no window" so local and
            # global layers can share one scanned body
            in_win = kpos[None, :] > qpos[:, None] - window
            valid &= jnp.logical_or(
                jnp.asarray(window) <= 0, in_win)
        s = jnp.where(valid[None, None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqs,bhsv->bhgqv", p.astype(vT.dtype), vT,
                       preferred_element_type=jnp.float32)
        return o                                     # (B, KVH, G, qc, Dv)

    out = jax.lax.map(lambda args: chunk_fn(*args),
                      (jnp.arange(nc), qr))          # (nc, B, KVH, G, qc, Dv)
    Dv = v.shape[-1]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nc * qc, H, Dv)
    return out[:, :Sq].astype(q.dtype)
