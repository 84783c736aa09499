#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip.  Not a benchmark.

Serves MiniCPM3-4B (MLA latent paged KV) at its published widths, in
bf16 with random weights from a seed, through ``Engine.serve`` and a
``make_trace`` arrival trace: requests share a base prompt (COW fork),
the pool is small enough to preempt (swap ``gather_blocks`` /
``scatter_blocks``) and one forced compaction runs ``copy_pool_blocks``.
Then Mamba2-370M (constant-state pool) serves a few requests with one
forced compaction, which runs ``block_copy`` on the flat state pool.
Every served token is checked against a one-request-at-a-time greedy
decode of the same prompt through the same model.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded decode on four chips only

``--four-chips`` runs a few full-width MiniCPM3-4B decode steps through
``launch.steps.build_serve_step`` on a ``(data=1, model=4)`` mesh and the
same steps on one chip, and nothing else.

The last line of stdout is one JSON object naming the device; any
failed phase exits non-zero first.  There is no CPU fallback: without a
TPU the script exits non-zero before it builds anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core.paged_kv import PagedKVCache, PagedKVManager  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.steps import build_serve_step  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.models.lm import DecoderLM  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402
from repro.serve.telemetry import compile_totals  # noqa: E402
from repro.serve.traffic import make_trace  # noqa: E402

SEED = 0

# A served token passes when its reference logit is within
# NEAR_TIE_SIGMAS standard deviations (of that step's reference logits)
# of the reference maximum.  With random weights the logits spread with
# sigma ~ sqrt(d_model) (~50 for MiniCPM3-4B) and bf16 rounds them to
# steps of 1-2 near the top, so top-2 near-ties are common and batch
# composition can flip them.  0.1 sigma is a few bf16 steps; a token
# read from the wrong cache blocks typically trails by sigmas.
NEAR_TIE_SIGMAS = 0.1


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu() -> None:
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {platform!r}, not 'tpu'; "
                 f"this smoke run needs a TPU chip and has no CPU fallback")


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def bytes_in_use(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


def tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# one-request-at-a-time references (teacher-forced on the served tokens)
# ---------------------------------------------------------------------------
def fresh_paged_cache(model, max_seq):
    """A one-sequence paged cache with a whole ``max_seq`` table."""
    kvcfg = model.kv_config(max_seq=max_seq, batch=1)
    mgr = PagedKVManager(kvcfg)
    mgr.admit(0, max_seq)
    return dataclasses.replace(
        PagedKVCache.create(kvcfg, 1),
        block_tables=jnp.asarray(mgr.device_table(0))[None])


def reference_logits(prefill, decode_step, state, params, prompt, tokens,
                     pad_to):
    """Logits of a single-sequence prefill + cached decode that chose
    ``tokens[i]`` at step i, feeding the served tokens back in."""
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :len(prompt)] = prompt
    last, state = prefill(params, {"tokens": jnp.asarray(toks)}, state,
                          jnp.asarray([len(prompt)], jnp.int32))
    out = [np.asarray(last[0], np.float32)]
    for t in tokens[:-1]:
        lg, state = decode_step(params, jnp.asarray([t], jnp.int32), state)
        out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


def near_tie_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """Per step: (reference max - reference logit of the served token),
    in units of that step's reference logit standard deviation."""
    picked = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return (ref_logits.max(-1) - picked) / ref_logits.std(-1)


def check_against_reference(name, model, params, done, max_seq):
    if isinstance(model, DecoderLM):         # its serving calls are jitted
        prefill, step = model.prefill, model.decode_step
        new_state = lambda: fresh_paged_cache(model, max_seq)
    else:
        prefill, step = jax.jit(model.prefill), jax.jit(model.decode_step)
        new_state = lambda: model.init_state(1)
    # one padded prompt width: a single prefill compile for all requests
    pad_to = max(-(-len(r.prompt) // 64) * 64 for r in done)
    steps = exact = 0
    worst = 0.0
    for req in sorted(done, key=lambda r: r.rid):
        if len(req.generated) != req.max_new:
            raise SmokeFailure(f"{name}: rid {req.rid} ended {req.state} "
                               f"with {len(req.generated)}/{req.max_new} "
                               f"tokens")
        ref = reference_logits(prefill, step, new_state(), params,
                               np.asarray(req.prompt), req.generated, pad_to)
        gaps = near_tie_gaps(ref, req.generated)
        ref_tokens = ref.argmax(-1)
        differ = np.nonzero(ref_tokens != np.asarray(req.generated))[0]
        if len(differ):
            i = int(differ[0])
            log(f"{name}: rid {req.rid} first differs from the reference at "
                f"step {i}: served {req.generated[i]}, reference "
                f"{int(ref_tokens[i])}, max logit difference "
                f"{ref[i].max() - ref[i, req.generated[i]]:.4f} "
                f"({gaps[i]:.4f} sigma)")
        steps += len(gaps)
        exact += len(gaps) - len(differ)
        worst = max(worst, float(gaps.max()))
        if gaps.max() > NEAR_TIE_SIGMAS:
            raise SmokeFailure(
                f"{name}: rid {req.rid} step {int(gaps.argmax())} served a "
                f"token {gaps.max():.4f} sigma below the reference maximum "
                f"(tolerance {NEAR_TIE_SIGMAS})")
    log(f"{name}: {exact}/{steps} served tokens equal the one-at-a-time "
        f"reference argmax; worst near-tie gap {worst:.4f} sigma "
        f"(tolerance {NEAR_TIE_SIGMAS})")


# ---------------------------------------------------------------------------
# served phases
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServePhase:
    arch: str
    requests: int
    slots: int
    num_blocks: int
    max_new: int
    trace: str
    shared_frac: float
    # The schedule (admissions, preemptions, forks, compaction) depends
    # on the trace and the pool geometry only, never on logits (eos_id
    # -1, no wall-clock prefill budget): a CPU run of a reduced model
    # with the same vocabulary and block size replays it exactly.  The
    # seed is one whose schedule preempts under this pool.
    trace_seed: int
    max_seq: int = 2048
    prompt_min: int = 64
    prompt_cap: int = 513
    # kernels the phase must have launched (transfer-plane counters)
    kernels: tuple = ()
    # engine counters that must be positive
    counters: tuple = ()


MINICPM3 = ServePhase(
    arch="minicpm3_4b", requests=12, slots=8, num_blocks=24, max_new=32,
    trace="poisson", shared_frac=0.5, trace_seed=1,
    kernels=("gather_blocks", "scatter_blocks", "copy_pool_blocks"),
    counters=("preemptions", "cow_copies", "prefix_hits"))
MAMBA2 = ServePhase(
    arch="mamba2_370m", requests=6, slots=4, num_blocks=6, max_new=16,
    trace="poisson", shared_frac=0.0, trace_seed=0,
    kernels=("block_copy",))


def serve_phase(phase: ServePhase, cfg) -> None:
    """Build ``cfg``, serve the phase's trace through ``Engine.serve``
    with one forced compaction after the first retirement, and check
    every served token against the one-at-a-time reference."""

    name = cfg.name
    t0 = time.perf_counter()
    model = build_model(cfg, max_positions=phase.max_seq)
    params, _ = model.init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    log(f"{name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.dtype}; parameter bytes {tree_bytes(params)}; "
        f"init {time.perf_counter() - t0:.2f} s")

    eng = Engine(model, params, slots=phase.slots, max_seq=phase.max_seq,
                 num_blocks=phase.num_blocks, eos_id=-1, prefill_budget=None)
    source = make_trace(phase.trace, phase.requests, cfg.vocab_size,
                        seed=phase.trace_seed, max_new=phase.max_new,
                        prompt_min=phase.prompt_min,
                        prompt_cap=phase.prompt_cap,
                        shared_frac=phase.shared_frac, n_bases=1)
    c0 = compile_totals()
    t0 = time.perf_counter()
    # serve step by step until the first retirement, compact once, then
    # serve the rest of the trace
    while not eng.done and eng.steps < 10_000:
        eng.serve(source, max_steps=eng.steps + 1)
    moved = eng.compact_now()
    done = eng.serve(source)
    wall = time.perf_counter() - t0
    c1 = compile_totals()

    st = eng.stats
    tokens = sum(len(r.generated) for r in done)
    launches = st["transfers"]["kernel_launches"]
    log(f"{name}: served {len(done)}/{phase.requests} requests, {tokens} "
        f"tokens in {eng.steps} steps; wall {wall:.2f} s including compiles "
        f"({tokens / wall:.2f} tokens/s, smoke run, not a measurement)")
    log(f"{name}: compile {c1[1] - c0[1]:.2f} s over {c1[0] - c0[0]} "
        f"compiles, {c1[2] - c0[2]} persistent-cache hits; in the step "
        f"loop's prefill {eng.telemetry.totals('serve.prefill').compiles}")
    log(f"{name}: preemptions {st['preemptions']}, COW copies "
        f"{st['cow_copies']}, prefix hits {st['prefix_hits']}, "
        f"compactions {st['compactions']} ({st['blocks_compacted']} blocks; "
        f"forced one moved {moved}), swap out/in bytes "
        f"{st['swap_out_bytes']}/{st['swap_in_bytes']}")
    log(f"{name}: transfer-plane kernel launches {launches}")

    if len(done) != phase.requests:
        raise SmokeFailure(f"{name}: {len(done)}/{phase.requests} served")
    if moved <= 0:
        raise SmokeFailure(f"{name}: the forced compaction moved no block")
    for k in phase.kernels:
        if launches.get(k, 0) < 1:
            raise SmokeFailure(f"{name}: kernel {k} never launched")
    for c in phase.counters:
        if st[c] < 1:
            raise SmokeFailure(f"{name}: counter {c} stayed at 0")

    check_against_reference(name, model, params, done, phase.max_seq)


# ---------------------------------------------------------------------------
# four chips: sharded decode (launch.steps.build_serve_step) vs one chip
# ---------------------------------------------------------------------------
def four_chip_phase(cfg, *, batch=4, max_seq=1024, prompt=256, steps=8):
    """Prefill on one chip, then decode ``steps`` teacher-forced tokens
    twice: on one chip, and sharded over a (data=1, model=4) mesh."""
    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(SEED))
    pbytes = tree_bytes(params)
    log(f"{cfg.name}: parameter bytes {pbytes}")
    kvcfg = model.kv_config(max_seq=max_seq, batch=batch)
    mbs = kvcfg.max_blocks_per_seq
    tables = np.arange(batch * mbs, dtype=np.int32).reshape(batch, mbs)
    cache = dataclasses.replace(PagedKVCache.create(kvcfg, batch),
                                block_tables=jnp.asarray(tables))
    rng = np.random.RandomState(SEED)
    toks = rng.randint(2, cfg.vocab_size, size=(batch, prompt + steps))
    _, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :prompt])},
                             cache, jnp.full((batch,), prompt, jnp.int32))

    mesh = make_host_mesh(model=4)
    step = build_serve_step(model, mesh, cache, donate=False)
    pshard, tshard, sshard = step.in_shardings
    # from a host copy: a device_put could alias the device-0 buffers
    # that the one-chip decode below donates
    cache4 = jax.device_put(jax.tree.map(np.asarray, cache), sshard)

    one = []
    for t in range(steps):
        lg, cache = model.decode_step(
            params, jnp.asarray(toks[:, prompt + t], jnp.int32), cache)
        one.append(np.asarray(lg, np.float32))
    params4 = jax.device_put(params, pshard)
    del params, cache
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(params4):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    held = [held[d] for d in devices]
    log(f"parameter bytes held per device: {held}; bytes in use per "
        f"device: {[bytes_in_use(d) for d in devices]}")
    four = []
    for t in range(steps):
        lg, cache4 = step(params4,
                          jax.device_put(jnp.asarray(toks[:, prompt + t],
                                                     jnp.int32), tshard),
                          cache4)
        four.append(np.asarray(lg, np.float32))
    one, four = np.stack(one), np.stack(four)

    if min(held) < 0.9 * pbytes / 4:
        raise SmokeFailure(f"parameters not spread over four devices: "
                           f"{held}")
    flat_one = one.reshape(-1, one.shape[-1])
    flat_four = four.reshape(-1, four.shape[-1])
    gaps = near_tie_gaps(flat_one, flat_four.argmax(-1))
    diff = np.abs(flat_one - flat_four).max(-1) / flat_one.std(-1)
    same = int((flat_one.argmax(-1) == flat_four.argmax(-1)).sum())
    log(f"four-chip vs one-chip decode: {same}/{len(gaps)} argmax equal; "
        f"max |logit difference| {diff.max():.4f} sigma; worst near-tie gap "
        f"{gaps.max():.4f} sigma (tolerance {NEAR_TIE_SIGMAS})")
    if gaps.max() > NEAR_TIE_SIGMAS:
        raise SmokeFailure("four-chip decode left the one-chip argmax "
                           "beyond the near-tie tolerance")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip decode check")
    args = ap.parse_args(argv)

    require_tpu()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"smoke run (not a benchmark) on {device['count']} x "
        f"{device['kind']}; compile cache {enable_compile_cache()}")
    compile_totals()                 # count from here
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(get_config(MINICPM3.arch))
    else:
        serve_phase(MINICPM3, get_config(MINICPM3.arch))
        log(f"peak bytes in use after {MINICPM3.arch}: {peak_bytes()}")
        serve_phase(MAMBA2, get_config(MAMBA2.arch))
    compiles, seconds, hits = compile_totals()
    log(f"total: compile {seconds:.2f} s over {compiles} compiles "
        f"({hits} persistent-cache hits); wall "
        f"{time.perf_counter() - t0:.2f} s; peak bytes in use "
        f"{peak_bytes()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
