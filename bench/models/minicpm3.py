"""MiniCPM3-4B's plain reference: the float32 latent-attention decoder
of ``bench/models/mla_decoder.py`` with MiniCPM3's own output head and
its muP multipliers, its weights, and the bytes one served decode step
must move.

Nothing here imports the program under test.

What differs from ``mla_decoder``'s equations:

    x   = E[tokens] * scale_emb
    x  += attn(x) * scale_depth / sqrt(L)        (each residual branch)
    x  += mlp(x)  * scale_depth / sqrt(L)
    logits = (rmsnorm(x) * (1 + final_norm)) * (dim_model_base / d) W_head

``W_head`` is a (d, vocab) matrix of its own, not the embedding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.common import head_rows, init_tree, pad, rms
from bench.models.mla_decoder import (EMBED_STD, Dims, _mm, _rope,
                                      decode_flops, prefill_flops,  # noqa: F401
                                      prefill_quantum)
from bench.models.mla_decoder import param_shapes as _tied_shapes


def _mup(cfg: dict):
    """(embedding multiplier, residual multiplier, head multiplier)."""
    return (float(cfg["scale_emb"]),
            cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers"]),
            cfg["dim_model_base"] / cfg["hidden_size"])


def param_shapes(cfg: dict) -> dict:
    """The served parameter tree: ``mla_decoder``'s, plus the head."""
    shapes = _tied_shapes(cfg)
    shapes["lm_head"] = (cfg["hidden_size"], cfg["vocab_size"])
    return shapes


def make_params(cfg: dict, key: jax.Array) -> dict:
    """Random weights, drawn in float32 and stored in the served dtype,
    as ``mla_decoder.make_params`` draws them; the head, like every other
    matrix, truncated normal with std fan_in**-0.5."""
    return init_tree(param_shapes(cfg), key, Dims.of(cfg).dtype,
                     plain_norms=("q_a_norm", "kv_a_norm"),
                     plus_one_norms=("ln1", "ln2", "final_norm"),
                     std={"embed": EMBED_STD})


@functools.partial(jax.jit, static_argnames=("m", "fp8", "res"))
def _layer(m: Dims, layers, i, x, fp8: bool, res: float):
    lp = jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, keepdims=False
                                               ).astype(jnp.float32), layers)
    a, f = lp["attn"], lp["ff"]
    T = x.shape[0]
    pos = jnp.arange(T)
    h = rms(x, lp["ln1"], m.eps, True)
    qa = rms(_mm(h, a["wq_a"], fp8), a["q_a_norm"], m.eps, False)
    q = _mm(qa, a["wq_b"], fp8).reshape(T, m.heads, m.nope + m.rope)
    q_nope, q_rope = q[..., : m.nope], _rope(q[..., m.nope:], pos, m.theta)
    kv = _mm(h, a["wkv_a"], fp8)
    c = rms(kv[:, : m.kv_lora], a["kv_a_norm"], m.eps, False)
    k_rope = _rope(kv[:, None, m.kv_lora:], pos, m.theta)[:, 0]
    k_nope = _mm(c, a["wk_b"], fp8).reshape(T, m.heads, m.nope)
    v = _mm(c, a["wv_b"], fp8).reshape(T, m.heads, m.v)
    scale = (m.nope + m.rope) ** -0.5
    outs = []
    for s0 in range(0, T, 512):                     # bounded score memory
        qn, qr = q_nope[s0:s0 + 512], q_rope[s0:s0 + 512]
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        qpos = s0 + jnp.arange(qn.shape[0])
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(outs, axis=0).reshape(T, m.heads * m.v)
    x = x + _mm(o, a["wo"], fp8) * res
    h = rms(x, lp["ln2"], m.eps, True)
    y = jax.nn.silu(_mm(h, f["wg"], fp8)) * _mm(h, f["wi"], fp8)
    return x + _mm(y, f["wo"], fp8) * res


@functools.partial(jax.jit, static_argnames=("emb",))
def _embed(params, tokens, emb: float):
    return params["embed"][tokens].astype(jnp.float32) * emb


@functools.partial(jax.jit, static_argnames=("m", "fp8", "mult"))
def _head(m: Dims, params, x, fp8: bool, mult: float):
    h = rms(x, params["final_norm"].astype(jnp.float32), m.eps, True) * mult
    return _mm(h, params["lm_head"].astype(jnp.float32), fp8)


def logits(cfg: dict, params, tokens: np.ndarray, start: int,
           fp8: bool = False, pad_to: int = 512) -> np.ndarray:
    """Float32 logits of one sequence at positions ``start..T-1``, layer
    by layer, as ``mla_decoder.logits`` computes them (``fp8`` the
    control), with MiniCPM3's head and multipliers."""
    m = Dims.of(cfg)
    emb, res, mult = _mup(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(params, jnp.asarray(pad(tokens, pad_to)), emb)
        for i in range(m.layers):
            x = _layer(m, params["layers"], jnp.int32(i), x, fp8, res)
        return head_rows(lambda c: _head(m, params, c, fp8, mult), x, start,
                         len(tokens), pad_to)


def decode_bytes(cfg: dict, kv_tokens: int, seqs: int) -> int:
    """Bytes one decode step of ``seqs`` sequences must move when they
    attend over ``kv_tokens`` cached tokens in all: every weight read
    once, but of the embedding only the ``seqs`` rows looked up (the
    head is a matrix of its own); each cached token's latent
    (``kv_lora + rope`` values a layer) read once; and each sequence's
    new latent written once.  Not what a paged decode moves over whole
    block tables: the least any decode must."""
    m = Dims.of(cfg)
    width = jnp.dtype(m.dtype).itemsize
    shapes = param_shapes(cfg)
    leaves = jax.tree.leaves({k: v for k, v in shapes.items()
                              if k != "embed"},
                             is_leaf=lambda s: isinstance(s, tuple))
    weights = (sum(math.prod(s) for s in leaves) + seqs * m.d) * width
    latent = m.layers * (m.kv_lora + m.rope) * width
    return weights + (kv_tokens + seqs) * latent
