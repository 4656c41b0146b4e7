"""A tiny cell for the tests: the real harness, runner and references
at a size the CPU runs in seconds, and the faults that break the timed
path underneath."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
PERFBENCH = HERE.parent
ROOT = PERFBENCH.parent
for p in (str(PERFBENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CONFS = {
    "attention": {
        "family": "attention", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "qkv_bias": True,
        "vocab_size": 4096, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": True},
    "mamba2": {
        "family": "mamba2", "d_model": 64, "n_layer": 2, "vocab_size": 4090,
        "pad_vocab_size_multiple": 16, "d_state": 16, "d_conv": 4,
        "expand": 2, "headdim": 16, "ngroups": 1, "chunk_size": 16,
        "norm_epsilon": 1e-5, "tie_embeddings": True},
}
TIE_KEYS = {"attention": "tie_word_embeddings", "mamba2": "tie_embeddings"}
CFGMODS = {"attention": "qwen2-1.5b.py", "mamba2": "mamba2-780m.py"}

TRAFFIC = {
    "runner": "serve", "arrivals": "open", "schedule_seed": 5,
    "rate_rps": 60.0, "max_requests": 3000, "lead_s": 0.3,
    "drain_cap_s": 20, "trace_s": 1,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
               "min": 8, "max": 48},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.3,
               "min": 4, "max": 10},
    "serve": {"batch": 4, "prefill_bucket": 16},
    "check": {"requests": 5, "pad_to": 16}}


def _token(sched, vocab):
    """A token altered where it is sampled."""
    sample = sched._sample
    sched._sample = lambda slot, row: (sample(slot, row) + 1) % vocab


def _state(sched, vocab):
    """A decode step that returns its cache unchanged."""
    decode = sched._decode
    sched._decode = lambda p, cache, tok, act: (
        decode(p, cache, tok, act)[0], cache)


def _half(sched, vocab):
    """The second half of the pool left out: its rows are the first
    half's."""
    decode = sched._decode

    def half(p, cache, tok, act):
        logits, cache = decode(p, cache, tok, act)
        b = logits.shape[0] // 2
        return logits.at[b:2 * b].set(logits[:b]), cache
    sched._decode = half


FAULTS = {"token": _token, "state": _state, "half": _half}


def context(family="attention", *, seed=3, seconds=1.0, control=False,
            traffic=None, limit=1.0, out_dir=None):
    spec = dict(TRAFFIC, **(traffic or {}))
    cell = {"name": f"tiny.{family}", "config": "tiny", "traffic": "tiny",
            "chips": 1}
    return harness.Context(
        cell=cell, conf=CONFS[family],
        cfgmod=harness._load_module(PERFBENCH / "configs" / CFGMODS[family]),
        traffic=spec, limits={"max_logit_gap": limit}, seed=seed,
        seconds=seconds, trace=False, control=control,
        t_process=time.perf_counter(),
        out_dir=out_dir or PERFBENCH / ".out" / "tests")


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def run(family="attention", *, fault=None, **kw) -> dict:
    """One run of the tiny cell through `harness.run_cell`; with
    `fault`, every scheduler the runner makes is broken by it."""
    ctx = context(family, **kw)
    b = bench()
    # the tiny cell reports the chat cells' metrics
    b["workloads"].append(ctx.cell)
    for m in b["end_to_end"] + b["per_layer"]:
        if "qwen2-1.5b.chat" in m.get("workloads", []):
            m["workloads"].append(ctx.cell["name"])
    mod = harness.load_runner(ctx.traffic["runner"])
    sound = mod.Runner
    if fault is not None:
        class Broken(sound):
            def make_scheduler(self):
                s = super().make_scheduler()
                FAULTS[fault](s, self.shapes.vocab)
                return s
        mod.Runner = Broken
    try:
        return harness.run_cell(b, ctx)
    finally:
        mod.Runner = sound
