"""Train step with int8 error-feedback gradient sync (distributed-
optimization feature for slow inter-pod links).

Structure: per-shard gradients are computed with ``jax.vmap`` over an
explicit leading shard dimension that is GSPMD-sharded over the data
axes -- each data shard computes the gradient of ITS microbatch, while
Megatron TP inside the loss still partitions over 'model' as usual.
The DP mean then goes through ``optim.compression.sync_mean`` (quantize
→ all_gather int8+scales → dequantize+average, residual kept per
device) inside a fully-manual ``shard_map`` -- 4x fewer DP sync bytes
on the wire than the f32 psum XLA would insert, with error feedback
making the quantization bias vanish across steps.

(A previous revision computed the per-shard gradients inside a shard_map
MANUAL over data / AUTO over 'model'; the partial-manual + collective
combination fatals in XLA on jax 0.4.x -- ``Check failed:
sharding.IsManualSubgroup()`` -- so the per-shard stage is expressed in
pure GSPMD and only the collective stage is manual, which is portable.)

At 2+ pod scale this is the collective that crosses the slow inter-pod
links every step, which is why it is worth compressing even though the
in-pod TP collectives stay full precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch import shardings as SH
from repro.launch.mesh import batch_axes
from repro.launch.steps import Step, opt_shardings, rules_for, _ns
from repro.optim import adamw as OPT
from repro.optim import compression as C


def residual_specs(params) -> jax.ShapeDtypeStruct:
    """Flat residual vector shape for a param tree (per data shard)."""
    n = 0
    for leaf in jax.tree.leaves(params):
        size = 1
        for d in leaf.shape:
            size *= d
        n += size + ((-size) % C.BLOCK)
    return jax.ShapeDtypeStruct((n,), jnp.float32)


def build_compressed_train_step(model, mesh: Mesh,
                                opt_cfg: OPT.AdamWConfig, *,
                                rules: Optional[Dict[str, Any]] = None,
                                remat: bool = True) -> Step:
    """Like build_train_step but with int8 DP gradient sync.

    Signature: step(params, opt_state, residual, batch) ->
               (params, opt_state, residual, metrics)
    residual: (n_dp_shards, L) f32 sharded over the data axes (each
    shard's error-feedback buffer).
    """
    rules = rules_for(model.cfg, mesh, rules)
    bax = batch_axes(mesh)
    ndp = 1
    for a in bax:
        ndp *= mesh.shape[a]
    pshapes, axes = model.param_specs()
    pshard = SH.param_shardings(axes, mesh, rules)
    oshard = opt_shardings(mesh, pshard, pshapes, zero1=False)

    def train_step(params, opt_state, residual, batch):
        # ---- stage 1: per-shard gradients, pure GSPMD ----
        # (ndp, B/ndp, ...) with the shard dim sharded over the data
        # axes: each data shard computes its own microbatch gradient.
        def shard_view(t):
            return t.reshape((ndp, t.shape[0] // ndp) + t.shape[1:])

        sbatch = jax.tree.map(shard_view, batch)

        def per_shard(p, local_batch):
            # under vmap the activation constraints may only reference
            # the non-data axes ('model'); batch stays unconstrained
            inner_rules = {**(rules or {}), "batch": None}

            def loss_fn(pp):
                with SH.use_rules(mesh, inner_rules):
                    return model.loss(pp, local_batch, remat=remat)

            (loss, mets), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            vec, _, _ = C.flatten_tree(grads)
            return vec, loss

        vecs, losses = jax.vmap(per_shard, in_axes=(None, 0))(params,
                                                              sbatch)
        vecs = jax.lax.with_sharding_constraint(vecs, _ns(mesh, bax))

        # ---- stage 2: int8 sync, fully-manual shard_map ----
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(bax), P(bax)), out_specs=(P(), P(bax)),
            check_vma=False)
        def sync(g, res):
            # HIERARCHICAL sync (measured, see §Perf): an int8 all_gather
            # over n shards moves n*bytes/4 on the wire -- WORSE than a
            # f32 ring all-reduce (2*bytes) once n > 8.  So: exact f32
            # pmean over the fast in-pod 'data' axis, int8+error-feedback
            # only across the slow 'pod' hop (n=2: 4x fewer inter-pod
            # bytes).  Falls back to int8-over-data when there is no pod
            # axis (small-DP case where it does win).
            if "pod" in bax and len(bax) > 1:
                inner = tuple(a for a in bax if a != "pod")
                vec = jax.lax.pmean(g[0], inner)
                mean_vec, new_res = C.sync_mean(vec, res[0], ("pod",))
            else:
                mean_vec, new_res = C.sync_mean(g[0], res[0], bax)
            return mean_vec, new_res[None]

        mean_vec, residual = sync(vecs, residual)
        _, treedef, shapes = C.flatten_tree(params)   # grads tree == params tree
        grads = C.unflatten_tree(mean_vec, treedef, shapes)
        params, opt_state, om = OPT.apply_updates(opt_cfg, params, grads,
                                                  opt_state)
        mets = {"loss": jnp.mean(losses), **om}
        return params, opt_state, residual, mets

    rshard = _ns(mesh, bax)
    jitted = jax.jit(
        train_step,
        in_shardings=(pshard, oshard, rshard, None),
        out_shardings=(pshard, oshard, rshard, None),
        donate_argnums=(0, 1, 2))
    return Step(jitted, mesh, rules, (pshard, oshard, rshard),
                (pshard, oshard, rshard))


def init_residual(params, mesh: Mesh):
    bax = batch_axes(mesh)
    ndp = 1
    for a in bax:
        ndp *= mesh.shape[a]
    spec = residual_specs(params)
    return jax.device_put(jnp.zeros((ndp, spec.shape[0]), jnp.float32),
                          _ns(mesh, bax))
