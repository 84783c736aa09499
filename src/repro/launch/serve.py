"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Thin driver over the continuous-batching request plane: builds a seeded
arrival trace (``repro.serve.traffic.make_trace``), feeds it to
``Engine.serve`` -- requests are admitted as they ARRIVE on the
engine's step clock and retired as they finish, the batch never drains
between requests -- and reports throughput, pool utilization, swap
traffic, prefix-share hits and per-tenant p50/p99 TTFT and inter-token
latency.

``--trace`` picks the arrival shape (poisson / bursty / heavytail /
static), ``--tenants`` spreads requests round-robin across tenants,
``--policy fair`` switches admission to per-tenant deficit-round-robin
fairness, and ``--deadline-slack`` attaches SLOs that steer the
deadline-cost preemption policy.  ``--shared-frac`` controls what
fraction of requests reuse one of a few base prompts (possibly
extended), exercising COW prefix sharing the way parallel sampling /
few-shot serving does.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serve.engine import Engine
from repro.serve.scheduler import FairAdmission
from repro.serve.traffic import TRACE_KINDS, make_trace


def _budget(v: str):
    """``--prefill-budget``: a positive int, 'auto', or 'none'."""
    if v == "auto":
        return "auto"
    if v in ("none", "None"):
        return None
    return int(v)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--num-blocks", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--trace", choices=TRACE_KINDS, default="poisson",
                    help="arrival shape fed to Engine.serve (virtual "
                         "step-clock arrivals; seeded and replayable)")
    ap.add_argument("--mean-gap", type=float, default=2.0,
                    help="mean inter-arrival gap in engine steps")
    ap.add_argument("--tenants", type=int, default=2,
                    help="requests assigned round-robin across tenants")
    ap.add_argument("--policy", choices=("fcfs", "fair"), default="fcfs",
                    help="admission order: FCFS (pinned default) or "
                         "per-tenant deficit-round-robin fairness")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="attach deadline = arrival + slack * max_new "
                         "(steers deadline-cost preemption)")
    ap.add_argument("--watermark", type=int, default=None,
                    help="free blocks kept as growth headroom (default: "
                         "adaptive from the observed growth EWMA)")
    ap.add_argument("--prefill-budget", type=_budget, default="auto",
                    help="max prompt tokens prefilled per step: an int, "
                         "'auto' (adaptive from measured latency; the "
                         "default) or 'none' (unlimited)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="synchronous transfers (drain per enqueue) "
                         "instead of the double-buffered schedule")
    ap.add_argument("--shared-frac", type=float, default=0.25,
                    help="fraction of requests sharing a base prompt")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, max_positions=args.max_seq)
    params, _ = model.init(jax.random.PRNGKey(args.seed))

    eng = Engine(model, params, slots=args.slots, max_seq=args.max_seq,
                 num_blocks=args.num_blocks, eos_id=-1,
                 watermark=args.watermark,
                 prefill_budget=args.prefill_budget,
                 admission_policy=(FairAdmission() if args.policy == "fair"
                                   else None),
                 overlap_transfers=not args.no_overlap)
    source = make_trace(args.trace, args.requests, cfg.vocab_size,
                        seed=args.seed, mean_gap=args.mean_gap,
                        tenants=args.tenants, max_new=args.max_new,
                        prompt_cap=min(32, args.max_seq // 2),
                        shared_frac=args.shared_frac,
                        deadline_slack=args.deadline_slack)
    t0 = time.time()
    done = eng.serve(source, max_steps=100_000)
    dt = time.time() - t0
    st = eng.stats
    toks = sum(len(r.generated) for r in done)
    print(f"served {len(done)}/{args.requests} requests "
          f"({args.trace} arrivals, {args.tenants} tenants, "
          f"{args.policy} admission), {toks} tokens in "
          f"{dt:.1f}s ({toks / max(dt, 1e-9):.1f} tok/s), "
          f"{eng.steps} engine steps, final pool util "
          f"{eng.mgr.utilization:.0%}")
    print(f"prefix-share hits {st['prefix_hits']}, COW copies "
          f"{st['cow_copies']}, preemptions {st['preemptions']}, "
          f"swap out/in {st['swap_out_bytes']}/{st['swap_in_bytes']} bytes")
    tr = st["transfers"]
    print(f"transfer plane: {tr['enqueued']} plans, "
          f"{tr['launches']} launches ({tr['coalesced']} coalesced), "
          f"{tr['overlapped']['d2h']} host copies + "
          f"{tr['overlapped']['h2d']} prefetch scatters overlapped decode "
          f"({st['prefetch_hits']} resumes served from prefetch), "
          f"effective watermark {st['watermark_effective']}")
    for tenant, row in eng.latency_report().items():
        def fmt(v):
            return "n/a" if v is None else f"{v:.1f}"
        print(f"  {tenant}: {row['requests']} requests, TTFT p50/p99 "
              f"{fmt(row['ttft_p50_ms'])}/{fmt(row['ttft_p99_ms'])} ms, "
              f"ITL p50/p99 {fmt(row['itl_p50_ms'])}/"
              f"{fmt(row['itl_p99_ms'])} ms")
    return done


if __name__ == "__main__":
    main()
