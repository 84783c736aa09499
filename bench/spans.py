#!/usr/bin/env python3
"""Run one benchmark cell once, as ``bench/run.py`` does, keeping the
program's span records of the measured window (``Engine.telemetry``
capture) in a JSON-lines file; or summarise such a file.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> --out <file>
    python3 bench/spans.py --summary <file>

The file's first line holds the window (``t0``, ``t1``) and the host
times of the traced stretch (``trace_host``); each further line is one
span record (``name``, ``t0``, ``t1``, ``parent`` and its attributes),
on the same clock.  The summary prints the prefill calls by sequences
per call, with their tokens, padding and compiles; for the steps that
decoded, the step period by the fused decode's update width ``W`` and
the phases of the fast and the slow steps; and the longest gaps between
consecutive steps with requests waiting.
"""

import time

T_START = time.perf_counter()          # set-up is timed from here

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PHASES = ("serve.fence", "serve.compact", "serve.admit", "serve.prefill",
          "serve.grow", "serve.transfer", "serve.sync", "serve.decode",
          "serve.retire")


def capture_window(out: Path) -> None:
    """Capture the spans of the window's ``drive`` (not the lead's)."""
    from bench import harness
    drive = harness.drive

    def captured(eng, arrivals, rec, t0, t_end, tracer):
        if t_end <= t0:
            return drive(eng, arrivals, rec, t0, t_end, tracer)
        eng.telemetry.capture(True)
        t1 = drive(eng, arrivals, rec, t0, t_end, tracer)
        records = eng.telemetry.capture(False)
        with open(out, "w") as f:
            f.write(json.dumps({"t0": t0, "t1": t1, "trace_host":
                                list(tracer.host) if tracer else None})
                    + "\n")
            for r in records:
                f.write(json.dumps(r) + "\n")
        return t1
    harness.drive = captured


def _steps(records):
    """Each step record with its child spans' seconds by name."""
    steps = sorted((r for r in records if r["name"] == "serve.step"),
                   key=lambda r: r["t0"])
    starts = [s["t0"] for s in steps]
    kids = [defaultdict(float) for _ in steps]
    attrs = [{} for _ in steps]
    for r in records:
        if r["name"] == "serve.step":
            continue
        i = bisect.bisect_right(starts, r["t0"]) - 1
        if i >= 0 and r["t1"] <= steps[i]["t1"]:
            kids[i][r["name"]] += r["t1"] - r["t0"]
            attrs[i].setdefault(r["name"], r)
    return steps, kids, attrs


def summary(path: Path) -> str:
    lines = path.read_text().splitlines()
    head, records = json.loads(lines[0]), [json.loads(x) for x in lines[1:]]
    steps, kids, attrs = _steps(records)
    out = [f"{len(steps)} steps in {head['t1'] - head['t0']:.3f} s; "
           f"traced stretch {head['trace_host']}"]
    pre = [r for r in records if r["name"] == "serve.prefill"]
    if pre:
        seqs = Counter(r.get("seqs") for r in pre)
        tokens = sum(r.get("tokens", 0) for r in pre)
        padded = sum(r.get("padded", r.get("tokens", 0)) for r in pre)
        med = statistics.median(r["t1"] - r["t0"] for r in pre)
        out.append(f"prefills {len(pre)}, by sequences a call "
                   f"{dict(sorted(seqs.items()))}, median {med * 1e3:.3f} "
                   f"ms; {tokens} tokens padded to {padded}; "
                   f"{sum(r.get('compiles', 0) for r in pre)} compiles")
    rows = []
    for i in range(len(steps) - 1):
        if "serve.decode" in kids[i]:
            rows.append((steps[i + 1]["t0"] - steps[i]["t0"], i))
    if rows:
        med = statistics.median(p for p, _ in rows)
        out.append(f"decode steps {len(rows)}, period median "
                   f"{med * 1e3:.3f} ms")
        by_w = defaultdict(list)
        for p, i in rows:
            by_w[attrs[i]["serve.decode"].get("W")].append((p, i))
        for w in sorted(by_w, key=lambda w: -1 if w is None else w):
            ps = [p for p, _ in by_w[w]]
            dec = [kids[i]["serve.decode"] for _, i in by_w[w]]
            batch = [steps[i].get("batch", 0) for _, i in by_w[w]]
            out.append(f"  W {w}: {len(ps)} steps, period median "
                       f"{statistics.median(ps) * 1e3:.3f} ms, decode median "
                       f"{statistics.median(dec) * 1e3:.3f} ms, batch "
                       f"{min(batch)}-{max(batch)}")
        for label, pick in (("fast", lambda p: p <= med),
                            ("slow", lambda p: p > med)):
            sel = [i for p, i in rows if pick(p)]
            if not sel:
                continue
            mean = {k: sum(kids[i][k] for i in sel) / len(sel) * 1e3
                    for k in PHASES}
            between = sum(steps[i + 1]["t0"] - steps[i]["t1"]
                          for i in sel) / len(sel) * 1e3
            out.append(f"  {label} half ({len(sel)}): " + ", ".join(
                f"{k[6:]} {v:.3f}" for k, v in mean.items())
                + f", between steps {between:.3f} ms")
    gaps = sorted(((steps[i + 1]["t0"] - steps[i]["t1"], i)
                   for i in range(len(steps) - 1)
                   if steps[i + 1].get("waiting", 0) > 0), reverse=True)
    if head["trace_host"]:
        out.append("  trace opened {:.3f} s, closed {:.3f} s into the "
                   "window".format(*(t - head["t0"]
                                     for t in head["trace_host"])))
    for g, i in gaps[:5]:
        out.append(f"  gap {g * 1e3:.3f} ms with requests waiting, after "
                   f"step {steps[i].get('step')} at "
                   f"{steps[i]['t1'] - head['t0']:.3f} s into the window")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--summary", type=Path)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    if args.summary:
        print(summary(args.summary))
        return 0
    from bench import harness
    capture_window(args.out)
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(summary(args.out), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
