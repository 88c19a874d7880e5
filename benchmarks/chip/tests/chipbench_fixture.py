"""A copy of the benchmark with more entries of each kind, made from the
files under ``fixtures/``: configurations, one of them with model code of
its own (``tiny-mla``, multi-head latent attention in two stages),
traffic mixes, a per-layer metric and the cells' limits.  Adding them
takes new files and new entries in BENCHMARK.json, and no edit to a file
that exists."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
FIXTURES = CHIP / "tests" / "fixtures"
CELL = "tiny-qwen3.tiny-chat"
THROUGHPUT_CELL = "tiny-qwen3.tiny-reasoning"
SESSIONS_CELL = "tiny-qwen3.tiny-sessions"
MLA_CELL = "tiny-mla.tiny-chat"

sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(REPO / "src"))


def make_checkout(tmp: pathlib.Path) -> pathlib.Path:
    """tmp/BENCHMARK.json and tmp/benchmarks/chip, with the fixture's
    files beside the real ones; returns tmp."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns(
        "runs", "__pycache__", "tests", "testdata"))
    for kind in ("configs", "traffic", "metrics", "checks", "code"):
        for f in (FIXTURES / kind).iterdir():
            assert not (here / kind / f.name).exists(), f
            shutil.copy(f, here / kind / f.name)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-qwen3",
        "source": "https://huggingface.co/Qwen/Qwen3-8B/blob/main/config.json",
        "file": "benchmarks/chip/configs/tiny-qwen3.json",
        "reduced": ["num_hidden_layers"], "why": "a CPU test of the harness"})
    spec["configs"].append({
        "name": "tiny-mla",
        "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/"
                  "main/config.json",
        "file": "benchmarks/chip/configs/tiny-mla.json",
        "reduced": json.loads((FIXTURES / "configs" / "tiny-mla.json")
                              .read_text())["reduced"],
        "why": "a CPU test of a second kind of model code"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-qwen3", "traffic": "tiny-chat",
        "chips": 1, "why": "a CPU test of the harness"})
    spec["workloads"].append({
        "name": THROUGHPUT_CELL, "config": "tiny-qwen3",
        "traffic": "tiny-reasoning", "chips": 1,
        "why": "a CPU test of the harness"})
    spec["workloads"].append({
        "name": MLA_CELL, "config": "tiny-mla", "traffic": "tiny-chat",
        "chips": 1, "why": "a CPU test of a second kind of model code"})
    spec["workloads"].append({
        "name": SESSIONS_CELL, "config": "tiny-qwen3",
        "traffic": "tiny-sessions", "chips": 1,
        "why": "a CPU test of the harness"})
    for m in spec["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"] += [CELL, SESSIONS_CELL, MLA_CELL]
        if m["name"] == "out_tokens_per_s":
            m["workloads"].append(THROUGHPUT_CELL)
    for m in spec["per_layer"]:
        if m["name"] in ("queue_wait_ms.chat", "idle_pct.chat"):
            m["workloads"].append(CELL)
        if m["name"] in ("batch_occupancy_pct.reasoning",
                         "pool_used_pct.reasoning", "mfu.decode"):
            m["workloads"].append(THROUGHPUT_CELL)
    spec["per_layer"].append({
        "name": "ticks_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "ttft_p90_ms",
        "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return tmp
