"""Per-arch smoke tests: reduced config, one forward + one train step on
CPU, asserting output shapes and finiteness."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_config
from repro.models.api import build_model, make_concrete_batch
from repro.optim import adamw as OPT

B, S = 2, 16


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, max_positions=S)
    params, axes = model.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, B, S)

    logits, aux, _ = model.forward(params, batch)
    toks = batch["tokens"].shape[1]
    exp_seq = toks + (cfg.num_image_tokens or 0)
    assert logits.shape == (B, exp_seq, cfg.vocab_size), arch
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch

    opt_cfg = OPT.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    opt_state = OPT.init_state(params)

    def step(p, o, b):
        (loss, mets), grads = jax.value_and_grad(
            lambda pp: model.loss(pp, b), has_aux=True)(p)
        p, o, _ = OPT.apply_updates(opt_cfg, p, grads, o)
        return p, o, loss

    p2, o2, loss = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(loss)), arch
    # parameters actually moved
    moved = any(
        not np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_match_init(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, max_positions=S)
    params, _ = model.init(jax.random.PRNGKey(0))
    shapes, axes = model.param_specs()
    real = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    spec = jax.tree.map(lambda x: (x.shape, str(x.dtype)), shapes)
    assert real == spec, arch
    # every param leaf has a logical-axes annotation of matching rank
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_ax = {tuple(str(k) for k in path): v for path, v in
               jax.tree_util.tree_leaves_with_path(
                   axes, is_leaf=lambda t: isinstance(t, tuple))}
    for path, leaf in flat_p:
        key = tuple(str(k) for k in path)
        assert key in flat_ax, (arch, key)
        assert len(flat_ax[key]) == leaf.ndim, (arch, key)


def test_loss_decreases_qwen_moe():
    """A few steps on a fixed batch must reduce loss (end-to-end sanity
    including router + grouped matmul gradients)."""
    cfg = get_config("qwen3_moe_30b_a3b").reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = make_concrete_batch(cfg, 2, 16)
    opt_cfg = OPT.AdamWConfig(lr_peak=3e-3, warmup_steps=1, total_steps=30,
                              weight_decay=0.0)
    opt = OPT.init_state(params)

    @jax.jit
    def step(p, o):
        (loss, _), g = jax.value_and_grad(
            lambda pp: model.loss(pp, batch), has_aux=True)(p)
        p, o, _ = OPT.apply_updates(opt_cfg, p, g, o)
        return p, o, loss

    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses
