"""The ``minicpm3-chat`` cell: a tiny twin of its configuration and
traffic (same ``arch``, the program's reduced MiniCPM3 config) served
through a whole run of the harness on the CPU; ``decode_bytes`` against
a count made by hand at the published widths; and the three readers of
the cell's translation metrics on synthetic spans."""

import json
import time
import types
from pathlib import Path

import pytest

from bench import harness, program_trace
from bench.metrics import (decode_hbm_roofline, kv_read_amplification,
                           table_sync_bytes_per_step)
from bench.models import minicpm3
from bench.program_trace import HostSpan, build

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "minicpm3-4b-chat.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "mla-chat.json").read_text())
MS = 1_000_000                                   # ns

# the program's reduced MiniCPM3 (``ModelConfig.reduced``), under the
# published config's keys; every field the cell checks still checked
TINY = {"hidden_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 256, "vocab_size": 512, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "torch_dtype": "float32"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A root holding only the twin: 4 slots of 64 tokens (8 blocks of 8
    each, and the sink), prompts 16-32 and outputs 6-24, within
    ``max_seq``."""
    root = tmp_path_factory.mktemp("minicpm3-cell")
    cfg = dict(CONFIG, **TINY)
    cfg["program"] = dict(CONFIG["program"], reduced=True)
    cfg["serving"] = dict(CONFIG["serving"], slots=4, max_seq=64,
                          num_blocks=33, prefill_budget=64)
    cfg["check"] = dict(CONFIG["check"], sample_tokens=40, min_tokens=16,
                        logit_gap_limit=0.01)
    mix = dict(TRAFFIC, rate_per_s=10.0, lead_seconds=0.5,
               prompt_tokens=dict(TRAFFIC["prompt_tokens"], median=24,
                                  min=16, max=32),
               output_tokens=dict(TRAFFIC["output_tokens"], median=12,
                                  min=6, max=24))
    assert cfg["arch"] == "minicpm3" and cfg["serving"]["prefetch"] is False
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= cfg["serving"]["max_seq"])
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-mla-chat.json").write_text(
        json.dumps(mix))
    spec = {"configs": [{"name": cfg["name"],
                         "file": "bench/configs/tiny.json"}],
            "workloads": [{"name": "tiny-minicpm3-chat",
                           "config": cfg["name"],
                           "traffic": "tiny-mla-chat", "chips": 1}],
            "end_to_end": [], "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_tiny_twin_serves_correctly(tiny_root):
    r = harness.run("tiny-minicpm3-chat", 2**33 + 15, 4.0, False,
                    t_start=time.perf_counter(), require_chip=False,
                    read_metrics=False, root=tiny_root)
    c = r["checks"]
    assert r["correct"], c
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["served_tokens_checked"]["value"] >= 16


def test_decode_bytes_at_published_widths():
    # parameters a step must read: the head 2,560 * 73,448 = 188,026,880,
    # final norm 2,560; a layer 2,560*768 + 768 + 768*40*96 + 2,560*288
    #   + 256 + 2 * 256*40*64 + 40*64*2,560 + 3 * 2,560*6,400 + 2 * 2,560
    #   = 62,674,944; 62 layers 3,885,846,528.  4,073,875,968 in all, in
    # bf16 8,147,751,936 bytes; of the embedding one 2,560-wide row a
    # sequence, 5,120 bytes.  A token's latent in every layer:
    # 62 * (256 + 32) * 2 = 35,712 bytes.
    assert minicpm3.decode_bytes(CONFIG, 0, 0) == 8_147_751_936
    assert minicpm3.decode_bytes(CONFIG, 1000, 4) == (
        8_147_751_936 + 4 * 5_120 + 1004 * 35_712)


def _steps(attrs):
    """Two 100 ms steps, each with a 2 ms sync and an 80 ms decode."""
    spans = []
    for t, (sync, dec) in zip((0, 150), attrs):
        spans += [HostSpan("serve.step", t * MS, (t + 100) * MS, {}),
                  HostSpan("serve.sync", (t + 12) * MS, (t + 14) * MS, sync),
                  HostSpan("serve.decode", (t + 14) * MS, (t + 94) * MS,
                           dec)]
    return build((0, 300 * MS), [spans], [[(14 * MS, 94 * MS)]])


def _ctx():
    return types.SimpleNamespace(arch=minicpm3, cell=types.SimpleNamespace(
        cfg=CONFIG), peaks={"hbm_bytes_per_s": 819e9})


def test_translation_readers(monkeypatch):
    table = 64 * 32 * 64
    pt = _steps([({"rows": 2, "bytes": 544},
                  {"seqs": 40, "kv_tokens": 20_000, "table_tokens": table}),
                 ({"rows": 0, "bytes": 0},
                  {"seqs": 41, "kv_tokens": 20_041, "table_tokens": table})])
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: pt)
    ctx = _ctx()
    assert kv_read_amplification.read(ctx) == pytest.approx(
        2 * table / 40_041)
    assert table_sync_bytes_per_step.read(ctx) == 272.0
    moved = (minicpm3.decode_bytes(CONFIG, 20_000, 40)
             + minicpm3.decode_bytes(CONFIG, 20_041, 41))
    assert decode_hbm_roofline.read(ctx) == pytest.approx(
        100 * moved / 0.160 / 819e9)


def test_translation_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    pt = _steps([({}, {"W": 2}), ({}, {"W": 0})])
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: pt)
    for reader in (decode_hbm_roofline, kv_read_amplification,
                   table_sync_bytes_per_step):
        assert reader.read(_ctx()) is None
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: None)
    for reader in (decode_hbm_roofline, kv_read_amplification,
                   table_sync_bytes_per_step):
        assert reader.read(_ctx()) is None
