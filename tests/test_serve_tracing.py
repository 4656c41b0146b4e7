"""Scheduler instrumentation: the spans it writes into a `jax.profiler`
trace, its batch-shape counters, the timestamps on each `Completion`,
and the stable names of its programs.

One short traced run per posture (contiguous, paged, chunked,
speculative) is shared by the tests of this module."""

import dataclasses
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve_lib import serve as serve_lib
from repro.serve_lib.scheduler import Request, Scheduler

BATCH = 2
BUCKET = 4
K = 2  # speculate_k
POSTURES = {
    "contiguous": {},
    "paged": {"cache_layout": "paged", "page_size": 8},
    "chunked": {"prefill_chunk": 8},
    "speculative": {"speculate_k": K, "draft": "self"},
}
# (prompt length, new tokens): more requests than slots, some prompts
# longer than a chunk
SHAPES = [(5, 4), (19, 3), (11, 5), (3, 2), (14, 4)]
PROGRAMS = ("_prefill", "_decode", "_chunk_prefill", "_verify", "_propose",
            "_advance", "_dprefill")
LEAVES = ("serve.stage", "serve.pull", "serve.sample")


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    meta: dict
    children: list = dataclasses.field(default_factory=list)

    def names(self) -> list[str]:
        return [c.name for c in self.children]


@dataclasses.dataclass
class Served:
    sched: Scheduler
    comps: dict
    steps: list          # serve.step spans, each with its subtree
    calls: dict          # program attribute -> [(args, kwargs), ...]
    programs: dict       # program attribute -> the scheduler's own jit


def _recording(calls: list, fn):
    def call(*a, **kw):
        calls.append((a, kw))
        return fn(*a, **kw)
    return call


def _host_spans(trace_dir) -> list[Span]:
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [Span(e.name, int(e.start_ns), int(e.end_ns),
                             dict(e.stats))
                        for e in line.events if e.name.startswith("serve.")]
    return out


def _tree(spans: list[Span]) -> list[Span]:
    """Roots of the span forest; fails on spans that overlap without
    one holding the other."""
    roots: list[Span] = []
    stack: list[Span] = []
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack:
            assert s.end <= stack[-1].end, (
                f"{s.name} overlaps {stack[-1].name} without nesting")
            stack[-1].children.append(s)
        else:
            roots.append(s)
        stack.append(s)
    return roots


@functools.lru_cache(maxsize=None)
def _served(posture: str, trace_dir: str) -> Served:
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    scfg = serve_lib.ServeConfig(max_seq=48, batch=BATCH,
                                 compute_dtype=jnp.float32,
                                 cache_dtype=jnp.float32,
                                 **POSTURES[posture])
    sched = Scheduler(params, cfg, scfg, prefill_bucket=BUCKET)
    calls: dict = {}
    programs: dict = {}
    for attr in PROGRAMS:
        fn = getattr(sched, attr, None)
        if fn is not None:
            programs[attr] = fn
            calls[attr] = []
            setattr(sched, attr, _recording(calls[attr], fn))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab, p)
                    .astype(np.int32), max_new_tokens=g)
            for u, (p, g) in enumerate(SHAPES)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        comps = sched.run(reqs, max_steps=200)
    return Served(sched, comps, _tree(_host_spans(trace_dir)), calls,
                  programs)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return lambda posture: _served(
        posture, str(tmp_path_factory.getbasetemp() / f"trace-{posture}"))


def _expected_decode(prompt_len: int, n_new: int, k: int) -> tuple[int, int]:
    """(decode or verify calls, summed slot clocks) that one request
    takes: the first token comes from prefill, then each call emits k+1
    tokens (a self-draft is always accepted) up to the budget."""
    emitted, calls, rows = 1, 0, 0
    while emitted < n_new:
        calls += 1
        rows += prompt_len + emitted - 1
        emitted += min(k + 1, n_new - emitted)
    return calls, rows


@pytest.mark.parametrize("posture", list(POSTURES))
def test_span_tree(served, posture):
    """Every tick is one serve.step holding the admit/ingest/decode/spec
    spans, and each of those the stage -> pull -> sample leaves, in
    that order and properly nested (a per-pick prefill call holds its
    stage alone, but the last of its admit, which holds the one pull
    and sample); the spans' metadata agrees with the counters."""
    run = served(posture)
    assert len(run.steps) == run.sched.step_count
    assert all(s.name == "serve.step" for s in run.steps)
    per_pick = posture != "paged"
    seen: set[str] = set()
    rows = decode_slots = 0
    for step in run.steps:
        names = step.names()
        assert names, "a tick with no work"
        order = ["serve.admit", "serve.ingest", "serve.decode", "serve.spec"]
        assert all(n in order for n in names), names
        assert names == sorted(set(names), key=order.index), names
        for call in step.children:
            seen.add(call.name)
            kids = call.children
            if call.name == "serve.admit":
                # empty when every pick streams in by chunks
                assert kids or posture == "chunked"
                assert {c.name for c in kids} <= {
                    "serve.prefill", "serve.draft_prefill"}
            else:
                kids = [call]
            prefills = [k for k in kids if k.name == "serve.prefill"]
            for k in kids:
                seen.add(k.name)
                got = [c.name for c in k.children
                       if c.name != "serve.draft_prefill"]
                if k.name == "serve.draft_prefill":
                    assert got == ["serve.stage"]
                elif k.name == "serve.ingest" and len(got) == 2:
                    assert got == list(LEAVES[:2])  # no prompt completed
                elif per_pick and k.name == "serve.prefill" \
                        and k is not prefills[-1]:
                    assert got == ["serve.stage"]   # pulled with the last
                else:
                    assert got == list(LEAVES), (k.name, got)
                if k.name == "serve.prefill":
                    assert k.meta["rows"] == (
                        k.meta["width"] if per_pick
                        else BATCH * k.meta["width"]), k.meta
                    assert k.meta["picks"] == 1 or not per_pick
                if k.name in ("serve.prefill", "serve.ingest"):
                    rows += k.meta["rows"]
                if k.name in ("serve.decode", "serve.spec"):
                    decode_slots += k.meta["slots"]
                for leaf in k.children:
                    if leaf.name in LEAVES:
                        assert not leaf.children, leaf.children
                    else:  # a draft prefill after an ingest's sample
                        assert leaf.names() == ["serve.stage"]
    assert {"serve.admit", "serve.prefill"} <= seen
    want = {"contiguous": {"serve.decode"}, "paged": {"serve.decode"},
            "chunked": {"serve.decode", "serve.ingest"},
            "speculative": {"serve.spec", "serve.draft_prefill"}}[posture]
    assert want <= seen and not ({"serve.decode", "serve.spec"} - want) & seen
    st = run.sched.stats
    assert rows == st["prefill_rows"]
    assert decode_slots == st["decode_slots"]


@pytest.mark.parametrize("posture", list(POSTURES))
def test_batch_shape_counters(served, posture):
    """prefill_rows is batch x width over every prefill and chunk call:
    batch 1 for the single-shot prefill of the contiguous postures, the
    whole pool for the paged prefill, chunks and the draft's prefill;
    decode_slots and decode_kv_rows follow from each request's prompt
    length and emitted count."""
    run = served(posture)
    st = run.sched.stats
    shapes = {attr: [a[1].shape for a, _ in run.calls.get(attr, [])]
              for attr in ("_prefill", "_chunk_prefill", "_dprefill")}
    single = 1 if posture != "paged" else BATCH
    assert shapes["_prefill"] and all(
        b == single for b, _ in shapes["_prefill"])
    assert all(b == BATCH for b, _ in shapes["_chunk_prefill"])
    assert all(b == BATCH for b, _ in shapes["_dprefill"])
    assert bool(shapes["_dprefill"]) == (posture == "speculative")
    assert st["prefill_rows"] == sum(
        b * w for attr in ("_prefill", "_chunk_prefill")
        for b, w in shapes[attr])
    assert st["prefill_tokens"] == sum(p for p, _ in SHAPES)
    assert st["prefill_tokens"] < st["prefill_rows"]
    k = POSTURES[posture].get("speculate_k", 0)
    calls = rows = 0
    for uid, (p, g) in enumerate(SHAPES):
        assert len(run.comps[uid].tokens) == g
        c, r = _expected_decode(p, g, k)
        calls, rows = calls + c, rows + r
    assert st["decode_slots"] == calls
    assert st["decode_kv_rows"] == rows
    if not k:
        assert st["decode_slots"] == st["decode_tokens"]
    assert st["decode_slots"] <= st["decode_steps"] * BATCH


@pytest.mark.parametrize("posture", list(POSTURES))
def test_completion_timestamps(served, posture):
    """submitted <= admitted <= first token <= finished for every
    request; a request that waited for a slot is admitted no earlier
    than the first request finished."""
    run = served(posture)
    comps = [run.comps[u] for u in range(len(SHAPES))]
    for c in comps:
        assert c.submitted_s <= c.admitted_s <= c.first_token_s \
            <= c.finished_s, c
    first_done = min(c.finished_s for c in comps[:BATCH])
    assert all(c.admitted_s >= first_done for c in comps[BATCH:])


@pytest.mark.parametrize("posture,attr,name", [
    ("contiguous", "_prefill", "serve_prefill"),
    ("contiguous", "_decode", "serve_decode"),
    ("chunked", "_chunk_prefill", "serve_chunk_prefill"),
    ("paged", "_prefill", "serve_prefill"),
    ("paged", "_decode", "serve_decode"),
    ("speculative", "_verify", "serve_verify"),
    ("speculative", "_propose", "serve_propose"),
    ("speculative", "_advance", "serve_advance"),
    ("speculative", "_dprefill", "serve_draft_prefill"),
])
def test_program_names(served, posture, attr, name):
    """The scheduler's programs lower under stable names, so device time
    groups under them in a trace."""
    run = served(posture)
    args, kw = run.calls[attr][-1]
    text = run.programs[attr].lower(*args, **kw).as_text()
    assert text.startswith(f"module @jit_{name} "), text.split("\n", 1)[0]
