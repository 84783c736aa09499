import dataclasses
import os

# Tests run single-device.  Distributed tests spawn subprocesses with their
# own XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def greedy_reference(model, params, prompt, max_new, quantum, max_seq=64):
    """Greedy decode of ONE request by the model alone: ``prefill`` of the
    prompt right-padded to a multiple of ``quantum`` (the serving
    strategy's ``prefill_quantum``), then ``decode_step`` a token at a
    time over the model's own state -- a one-sequence ``PagedKVCache``
    for paged models, ``init_state(1)`` for SSM and RWKV6,
    ``init_state(1, max_seq)`` for the Zamba2 hybrid.  No engine, Arena,
    pool or block table of the serving stack is involved."""
    import jax.numpy as jnp
    from repro.core.paged_kv import PagedKVCache

    def own_tables(kv):
        tables = jnp.arange(kv.config.max_blocks_per_seq, dtype=jnp.int32)
        return dataclasses.replace(kv, block_tables=tables[None])

    if not hasattr(model, "init_state"):
        state = own_tables(PagedKVCache.create(
            model.kv_config(max_seq=max_seq, batch=1), 1))
    elif hasattr(model, "kv_config"):
        state = model.init_state(1, max_seq)
        state = dataclasses.replace(state, kv=own_tables(state.kv))
    else:
        state = model.init_state(1)
    n = len(prompt)
    toks = jnp.asarray(np.pad(np.asarray(prompt), (0, -n % quantum)))[None]
    last, state = model.prefill(params, {"tokens": toks}, state,
                                jnp.asarray([n], jnp.int32))
    out = [int(jnp.argmax(last[0]))]
    for _ in range(max_new - 1):
        logits, state = model.decode_step(params, jnp.asarray([out[-1]]),
                                          state)
        out.append(int(jnp.argmax(logits[0])))
    return out


def assert_engine_quiescent(eng):
    """Suite-wide leak invariant for serving-engine tests.

    After a workload fully drains, the unified Arena must be back to
    zero non-pinned blocks used, an all-zeros refcount histogram (no
    stranded COW shares) and an empty host swap tier -- in every pool
    class (KV, scheduler metadata, ...).  Engine tests call this as
    their last line so allocator leaks fail loudly at the test that
    introduced them.
    """
    assert not eng.running, f"sequences still running: {eng.running}"
    assert not eng.sched.has_work, "scheduler still has queued work"
    eng.arena.assert_quiescent()
