"""The trip-count-aware HLO cost model vs analytically known programs."""

import numpy as np
import jax
import jax.numpy as jnp

from repro import cost as cost_model


def _cost(fn, *specs):
    comp = jax.jit(fn).lower(*specs).compile()
    return cost_model.analyze_text(comp.as_text()), comp


def test_scan_matmul_flops_exact():
    L, B, D = 7, 32, 64

    def f(ws, x):
        def body(x, w):
            return x @ w, None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    cost, comp = _cost(f, jax.ShapeDtypeStruct((L, D, D), jnp.float32),
                       jax.ShapeDtypeStruct((B, D), jnp.float32))
    expected = L * 2 * B * D * D
    assert abs(cost.flops - expected) / expected < 0.01
    # XLA's own counter misses the trip count (documents the motivation);
    # xla_cost_analysis normalizes the list-vs-dict return across versions
    xla = cost_model.xla_cost_analysis(comp)
    assert xla["flops"] < 0.5 * expected


def test_grad_flops_3x():
    L, B, D = 5, 16, 32

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return (x ** 2).sum()

    fwd, _ = _cost(f, jax.ShapeDtypeStruct((L, D, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D), jnp.float32))
    bwd, _ = _cost(jax.grad(f, argnums=0),
                   jax.ShapeDtypeStruct((L, D, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, D), jnp.float32))
    ratio = bwd.flops / fwd.flops
    assert 2.5 < ratio < 3.5, ratio


def test_nested_scan_trip_multiplication():
    Lo, Li, D = 4, 6, 16

    def f(w, x):
        def outer(x, _):
            def inner(x, __):
                return x @ w, None
            x, _ = jax.lax.scan(inner, x, None, length=Li)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=Lo)
        return x.sum()

    cost, _ = _cost(f, jax.ShapeDtypeStruct((D, D), jnp.float32),
                    jax.ShapeDtypeStruct((8, D), jnp.float32))
    expected = Lo * Li * 2 * 8 * D * D
    assert abs(cost.flops - expected) / expected < 0.01


def test_collective_bytes_detected():
    from jax.sharding import NamedSharding, PartitionSpec as P
    if jax.device_count() < 2:
        import pytest
        pytest.skip("needs >1 device")


def test_dynamic_update_slice_not_overcounted():
    N = 1 << 20

    def f(big, small):
        def body(b, i):
            return jax.lax.dynamic_update_index_in_dim(
                b, small, i * 16, 0), None
        b, _ = jax.lax.scan(body, big, jnp.arange(8))
        return b.sum()

    cost, _ = _cost(f, jax.ShapeDtypeStruct((N,), jnp.float32),
                    jax.ShapeDtypeStruct((16,), jnp.float32))
    # traffic must be ~N (the final sum), not 8 * N from the DUS loop
    assert cost.bytes < 6 * N * 4, cost.bytes


def test_shape_bytes_tuple():
    s = "(s32[], f32[4,8]{1,0}, bf16[2,2]{1,0})"
    assert cost_model.shape_bytes(s) == 4 + 128 + 8
