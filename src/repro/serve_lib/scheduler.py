"""Continuous-batching serve scheduler over one persistent KV cache.

The static-batch `serve.generate` loop pads every request to one
rectangle: same-length prompts only, finished sequences burn decode
compute until the longest one ends, and new requests wait for the whole
batch to drain.  ReDas's own lesson — reconfigure per layer instead of
padding work to a fixed shape — applies to the serving plane too, and
the model layer already supports it: `flash_attention` takes per-slot
`q_pos`/`kv_len`, and the cache clock `cache["t"]` is a per-slot vector.

`Scheduler` owns a fixed pool of `ServeConfig.batch` slots over ONE
persistent cache:

  admit   queued requests enter free slots by prefill.  On the
          contiguous cache each pick runs its own batch-1 prefill over
          its slot alone, at its own bucketed width: the slot's leaves
          are sliced out of the pool's cache, prefilled and written
          back, in-flight slots untouched.  The paged cache (block
          tables, shared-prefix history) and chunked ingestion keep one
          ragged call over the whole pool
          (`transformer.prefill(lengths=..., update_mask=...)`).  The
          first output token is sampled from the prefill logits.
  decode  one fused `decode_step` over the whole pool with an `active`
          mask — the call shapes NEVER change, so the jitted step (and
          the `repro.engine` decision cache behind it) is reused for
          every step the scheduler ever takes.
  evict   EOS / max-tokens frees the slot immediately for the next
          queued request; no cache scrubbing is needed because a slot's
          clock masks stale rows and the next admit overwrites its
          recurrent state.

Prefill is the only shape-variable call: prompt widths are rounded up
to `prefill_bucket` (1 = exact prompt length — bitwise-parity mode;
larger buckets bound jit retraces to O(max_seq / bucket) distinct
widths).  The slot index of the per-pick prefill is traced, so one
program per width serves every slot and every number of picks.

Chunked prefill (`ServeConfig.prefill_chunk`, DESIGN.md §12) bounds the
other head-of-line blocker: without it, one long arriving prompt
monopolizes a whole tick, stalling every in-flight decode for the full
prompt's prefill latency.  With a chunk size set, a long prompt streams
into its slot `prefill_chunk` tokens per tick (`transformer.prefill`'s
`hist_len` continuation — exact for all four cache kinds), each chunk
sharing its tick with the pool's fused decode, so in-flight slots keep
emitting.  `Scheduler.serve_async()` wraps the tick loop in a worker
thread behind a bounded request queue for callers that want submission
decoupled from stepping.

Greedy outputs match per-request `serve.generate` exactly for every
cache kind; the one caveat is MoE capacity dropping: expert capacity
scales with the call's padded width, so at drop-inducing capacity
factors an MoE request's dropped tokens can depend on its own bucketed
width (or, on the paged layout, its admit group's) — the width
dependence the static `generate` path already has versus `forward`.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import functools
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import engine as engine_mod
from repro.models import transformer as T
from repro.models.config import ArchConfig

from . import serve as serve_lib
from .paged import PagedKV, PoolExhausted


@dataclasses.dataclass
class Request:
    """One generation request: `prompt` (L,) int32, emit up to
    `max_new_tokens` (stopping early at `eos_id` if given)."""
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    key: jax.Array | None = None
    eos_id: int | None = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: np.ndarray           # (n_emitted,) int32
    finish_reason: str           # "length" | "eos"
    prompt_len: int
    admit_step: int
    finish_step: int
    # time.perf_counter() readings: `submit`, the pick in `_admit`, the
    # first and the last emitted token (under chunked ingestion the
    # first token comes with the final chunk)
    submitted_s: float
    admitted_s: float
    first_token_s: float
    finished_s: float


@dataclasses.dataclass
class _Slot:
    req: Request
    key: jax.Array | None
    emitted: list[int]
    last_token: int
    admit_step: int
    submitted_s: float
    admitted_s: float
    first_token_s: float = 0.0
    # chunked ingestion (DESIGN.md §12): tokens of the prompt already
    # resident in the cache (shared-prefix pages included); while
    # `ingesting` the slot sits out of decode ticks and receives one
    # chunk per `_ingest_tick` until the whole prompt is resident.
    ingest_pos: int = 0
    ingesting: bool = False


@functools.lru_cache(maxsize=64)
def _jitted_steps(cfg: ArchConfig, scfg: serve_lib.ServeConfig, engine,
                  paged: bool = False):
    """One jitted (ragged prefill, masked decode) pair per posture, so
    every Scheduler instance over the same configs reuses the traced
    executables.  The engine joins the key because traces bind the
    engine context active when first taken (DESIGN.md §3).  The paged
    pair additionally threads the block tables (and the shared-prefix
    history: `hist_pages` is static — one retrace per distinct history
    page count, same O(max_seq / page) bound the prefill widths have).

    The third element is the chunk-continuation prefill (DESIGN.md
    §12): the contiguous layout needs a separate trace that threads
    `hist_len`; the paged prefill already does (chunk history rides the
    same gathered-pages path shared prefixes use), so there the chunk
    step IS the admit step.

    The contiguous prefill runs ONE slot: it takes the whole cache and
    a traced slot index, slices that slot out of every leaf, prefills
    it at batch 1 and writes it back.  One program per width serves
    every slot; every other slot is untouched.

    Each jit wraps a named function, so its programs carry a stable
    name (`jit_serve_prefill`, ...) in compiler dumps and device
    traces."""
    if paged:
        def serve_prefill(p, tok, cache, lens, mask, bt, hist, *,
                          hist_pages):
            return T.prefill(p, cfg, tok, cache,
                             compute_dtype=scfg.compute_dtype, lengths=lens,
                             update_mask=mask, block_tables=bt,
                             hist_len=hist, hist_pages=hist_pages)

        def serve_decode(p, cache, tok, act, bt):
            return T.decode_step(p, cfg, cache, tok,
                                 compute_dtype=scfg.compute_dtype,
                                 active=act, block_tables=bt)

        prefill = jax.jit(serve_prefill, static_argnames=("hist_pages",))
        return prefill, jax.jit(serve_decode), prefill

    def serve_prefill(p, tok, cache, lens, i):
        # slots leaves are (n_periods, B, ...); tail leaves and t (B, ...)
        def slot(a, ax):
            return jax.lax.dynamic_slice_in_dim(a, i, 1, ax)

        def put(a, new, ax):
            return jax.lax.dynamic_update_slice_in_dim(
                a, new.astype(a.dtype), i, ax)

        one = {"t": slot(cache["t"], 0),
               "slots": jax.tree.map(lambda a: slot(a, 1), cache["slots"]),
               "tail": jax.tree.map(lambda a: slot(a, 0), cache["tail"])}
        logits, new = T.prefill(p, cfg, tok, one,
                                compute_dtype=scfg.compute_dtype,
                                lengths=lens)
        return logits, {
            "t": put(cache["t"], new["t"], 0),
            "slots": jax.tree.map(lambda a, n: put(a, n, 1),
                                  cache["slots"], new["slots"]),
            "tail": jax.tree.map(lambda a, n: put(a, n, 0),
                                 cache["tail"], new["tail"])}

    def serve_decode(p, cache, tok, act):
        return T.decode_step(p, cfg, cache, tok,
                             compute_dtype=scfg.compute_dtype, active=act)

    def serve_chunk_prefill(p, tok, cache, lens, mask, hist):
        return T.prefill(p, cfg, tok, cache,
                         compute_dtype=scfg.compute_dtype, lengths=lens,
                         update_mask=mask, hist_len=hist)

    return (jax.jit(serve_prefill), jax.jit(serve_decode),
            jax.jit(serve_chunk_prefill))


@functools.lru_cache(maxsize=64)
def _jitted_spec_steps(cfg: ArchConfig, dcfg: ArchConfig,
                       scfg: serve_lib.ServeConfig, engine,
                       paged: bool = False):
    """The speculative tick's jits (DESIGN.md §9): k-step greedy draft
    `propose` over a throwaway cache copy, fused k+1-wide `verify` of
    the target, `advance` replaying the verify window through the
    persistent draft cache, and the draft's own ragged prefill.  The
    draft cache is always contiguous (it is private per scheduler and
    never shares prefixes), so only `verify` has a paged variant.
    Named functions, as in `_jitted_steps`."""
    k = scfg.speculate_k
    if paged:
        def serve_verify(p, cache, toks, act, bt):
            return T.verify_step(p, cfg, cache, toks,
                                 compute_dtype=scfg.compute_dtype,
                                 active=act, block_tables=bt)
    else:
        def serve_verify(p, cache, toks, act):
            return T.verify_step(p, cfg, cache, toks,
                                 compute_dtype=scfg.compute_dtype,
                                 active=act)

    def serve_propose(p, cache, tok, act):
        return T.draft_propose(p, dcfg, cache, tok, k,
                               compute_dtype=scfg.compute_dtype, active=act)

    def serve_advance(p, cache, toks, keep, act):
        return T.spec_advance(p, dcfg, cache, toks, keep,
                              compute_dtype=scfg.compute_dtype, active=act)

    def serve_draft_prefill(p, tok, cache, lens, mask):
        return T.prefill(p, dcfg, tok, cache,
                         compute_dtype=scfg.compute_dtype, lengths=lens,
                         update_mask=mask)

    return (jax.jit(serve_verify), jax.jit(serve_propose),
            jax.jit(serve_advance), jax.jit(serve_draft_prefill))


class Scheduler:
    """Engine-aware continuous-batching loop over a slot pool.

    `params` must already be in serving dtype.  `engine` overrides the
    `ServeConfig`-derived one (`serve.warm_start_engine`); all jit
    traces happen inside its scope so every matmul shares one decision
    cache (`engine.plan.stats()` shows hits once shapes repeat)."""

    def __init__(self, params, cfg: ArchConfig, scfg: serve_lib.ServeConfig,
                 *, engine: "engine_mod.Engine | None" = None,
                 prefill_bucket: int = 1, draft_params=None,
                 draft_cfg: ArchConfig | None = None):
        if cfg.kind == "encoder":
            raise ValueError("encoder-only arch: no decode step")
        if cfg.embed_inputs or cfg.prefix_tokens:
            raise NotImplementedError(
                "scheduler serves token prompts only (no embeds/VLM prefix)")
        if prefill_bucket < 1:
            raise ValueError(f"prefill_bucket must be >= 1: {prefill_bucket}")
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("draft_params and draft_cfg come together")
        if draft_params is not None and not scfg.speculate_k:
            raise ValueError("draft_params needs ServeConfig(speculate_k>0)")
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.prefill_bucket = prefill_bucket
        self.engine = (engine if engine is not None
                       else serve_lib.warm_start_engine(scfg))
        self.cache = serve_lib.init_cache(cfg, scfg)
        # the paged plane is live only when the arch HAS full-attention
        # layers to page (on window/SSM/RG-LRU-only archs a paged
        # ServeConfig builds the identical contiguous cache and runs the
        # contiguous code path — paging those kinds buys nothing).
        # Prefix sharing needs EVERY layer's prompt state to live in
        # shareable pages, so it arms on pure-attention archs only.
        self.paged: PagedKV | None = None
        if scfg.cache_layout == "paged" and "attn" in cfg.layer_pattern:
            self.paged = PagedKV(
                batch=scfg.batch, max_seq=scfg.max_seq,
                page_size=scfg.page_size, n_pages=scfg.resolved_n_pages,
                prefix_sharing=set(cfg.layer_pattern) == {"attn"})
        self.slots: list[_Slot | None] = [None] * scfg.batch
        self.queue: collections.deque[Request] = collections.deque()
        self.completions: dict[int, Completion] = {}
        self.step_count = 0
        self.stats = {"admitted": 0, "finished": 0, "prefill_calls": 0,
                      "decode_steps": 0, "decode_tokens": 0,
                      "prefill_widths": set(),
                      # prefilled token/width totals: the FLOP-relevant
                      # counters prefix sharing drives DOWN (the PR 6
                      # bench's reuse ratio and the sharing tests key on
                      # these, like PR 4's decode-call counter).
                      # prefill_width_sum is PER-SLOT: each prefill call
                      # adds its width once per admitted slot, so
                      # bucketing mixed-history admits by hist_pages
                      # shows up as a drop (PR 7).  It is not what the
                      # device computes: that is prefill_rows, the rows
                      # of every prefill or chunk call (width for a
                      # per-pick call, batch x width for a whole-pool
                      # one; padding included)
                      "prefill_tokens": 0, "prefill_width_sum": 0,
                      "prefill_rows": 0, "shared_prefix_tokens": 0,
                      # decode/verify calls: active slots, and their
                      # resident context rows (each slot's clock as
                      # the call starts), summed over calls
                      "decode_slots": 0, "decode_kv_rows": 0,
                      # speculative plane (DESIGN.md §9)
                      "spec_ticks": 0, "draft_tokens": 0,
                      "accepted_draft_tokens": 0}
        self._live_uids: set[int] = set()
        self._submitted_s: dict[int, float] = {}  # queued uid -> submit
        self._prefill, self._decode, self._chunk_prefill = _jitted_steps(
            cfg, scfg, self.engine, self.paged is not None)
        # chunked ingestion (DESIGN.md §12): chunk calls are always
        # exactly `chunk` wide; aligning the chunk to the prefill bucket
        # keeps it inside the admit-width universe plan_arch pre-decides
        self.chunk = scfg.prefill_chunk
        if self.chunk is not None and self.chunk % prefill_bucket:
            raise ValueError(
                f"prefill_chunk {self.chunk} is not a multiple of "
                f"prefill_bucket {prefill_bucket}: the chunk width must "
                f"sit in the bucketed admit-width universe the engine "
                f"plan pre-decides (zero steady-state misses)")
        # -- speculative plane (DESIGN.md §9) -----------------------------
        self.spec_k = scfg.speculate_k
        self.draft_params = self.draft_cfg = self.draft_cache = None
        if self.spec_k:
            if draft_params is not None:
                self.draft_params, self.draft_cfg = draft_params, draft_cfg
            elif scfg.draft == "self-int8":
                from repro.quant import quantize_params
                self.draft_params, self.draft_cfg = quantize_params(params), cfg
            else:  # None / "self": share the target params outright
                self.draft_params, self.draft_cfg = params, cfg
            w = self.spec_k + 1
            for c in {cfg, self.draft_cfg}:
                if "local" in c.layer_pattern:
                    ring = min(c.window, scfg.max_seq)
                    if w > ring:
                        raise ValueError(
                            f"speculate_k={self.spec_k}: the k+1-wide "
                            f"verify writes {w} ring rows but the sliding "
                            f"window holds only {ring} — rollback could "
                            f"not restore a window it overwrote twice")
            # private contiguous float cache: the draft replays full
            # prompts and the accepted verify windows, sharing nothing
            self.draft_cache = T.init_cache(
                self.draft_cfg, T.CacheSpec(scfg.max_seq, scfg.batch),
                dtype=scfg.compute_dtype)
            self._verify, self._propose, self._advance, self._dprefill = (
                _jitted_spec_steps(cfg, self.draft_cfg, scfg, self.engine,
                                   self.paged is not None))

    # -- request intake ----------------------------------------------------

    def submit(self, req: Request) -> None:
        n = int(np.asarray(req.prompt).size)
        if n < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens < 1")
        if n + req.max_new_tokens > self.scfg.max_seq:
            raise ValueError(
                f"request {req.uid}: prompt {n} + max_new "
                f"{req.max_new_tokens} exceeds max_seq {self.scfg.max_seq}")
        if req.temperature > 0.0 and req.key is None:
            raise ValueError(
                f"request {req.uid}: temperature > 0 needs a PRNG key")
        if self.spec_k:
            if req.temperature > 0.0:
                raise ValueError(
                    f"request {req.uid}: speculative decoding is greedy-"
                    f"only (acceptance is computed in-graph via argmax; "
                    f"temperature sampling would need a host RNG round-"
                    f"trip per draft token)")
            if n + req.max_new_tokens + self.spec_k > self.scfg.max_seq:
                raise ValueError(
                    f"request {req.uid}: prompt {n} + max_new "
                    f"{req.max_new_tokens} + speculate_k {self.spec_k} "
                    f"exceeds max_seq {self.scfg.max_seq} — the verify "
                    f"pass writes k rows past the final token")
        if req.uid in self._live_uids:  # queued, in flight, or completed
            raise ValueError(f"duplicate request uid {req.uid}")
        self._live_uids.add(req.uid)
        self._submitted_s[req.uid] = time.perf_counter()
        self.queue.append(req)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _scope(self):
        return (engine_mod.use_engine(self.engine)
                if self.engine is not None else contextlib.nullcontext())

    # -- sampling (host-side, per slot: each request owns its key) ---------

    def _sample(self, slot: _Slot, logits_row: np.ndarray) -> int:
        if slot.req.temperature > 0.0:
            slot.key, sub = jax.random.split(slot.key)
            return int(jax.random.categorical(
                sub, jnp.asarray(logits_row) / slot.req.temperature))
        return int(np.argmax(logits_row))

    def _emit(self, i: int, tok: int, finished: list[Completion]) -> None:
        """Record one sampled token for slot i; evict on EOS/budget."""
        slot = self.slots[i]
        now = time.perf_counter()
        if not slot.emitted:
            slot.first_token_s = now
        slot.emitted.append(tok)
        slot.last_token = tok
        done_eos = slot.req.eos_id is not None and tok == slot.req.eos_id
        done_len = len(slot.emitted) >= slot.req.max_new_tokens
        if done_eos or done_len:
            comp = Completion(
                uid=slot.req.uid,
                tokens=np.asarray(slot.emitted, np.int32),
                finish_reason="eos" if done_eos else "length",
                prompt_len=int(np.asarray(slot.req.prompt).size),
                admit_step=slot.admit_step, finish_step=self.step_count,
                submitted_s=slot.submitted_s, admitted_s=slot.admitted_s,
                first_token_s=slot.first_token_s, finished_s=now)
            self.completions[slot.req.uid] = comp
            finished.append(comp)
            self.slots[i] = None  # slot free for the next queued request
            if self.paged is not None:
                # deref the slot's pages: private ones free immediately,
                # shared ones live on in other slots / the prefix index
                self.paged.release(i)
            self.stats["finished"] += 1

    # -- the batch calls ------------------------------------------------------
    #
    # Each call's host work runs under `jax.profiler.TraceAnnotation`
    # spans (README "Observing the scheduler"): `serve.stage` builds the
    # inputs and dispatches the program, `serve.pull` brings its output
    # to the host (where the host waits for the device), `serve.sample`
    # picks and records the tokens.  The three never nest, so device
    # idle inside one is counted once.

    def _admit(self, finished: list[Completion]) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        with TraceAnnotation("serve.admit"):
            self._admit_picks(free, finished)

    def _admit_picks(self, free: list[int],
                     finished: list[Completion]) -> None:
        picks: list[tuple[int, Request]] = []
        hists: dict[int, int] = {}
        if self.paged is not None:
            # peek-then-pop: PoolExhausted leaves the request queued
            # (backpressure — completions will free pages) instead of
            # dropping it.  Stuck with every slot free means the pool
            # genuinely cannot hold the prompt: fail with intent.
            while free and self.queue:
                i, req = free[0], self.queue[0]
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                try:
                    hists[i] = self.paged.admit(i, prompt.tolist())
                except PoolExhausted:
                    if not picks and self.n_active == 0:
                        raise RuntimeError(
                            f"page pool ({self.paged.n_pages} pages of "
                            f"{self.paged.page}) cannot hold request "
                            f"{req.uid}'s prompt ({prompt.size} tokens) "
                            f"even with every slot free — raise "
                            f"ServeConfig.n_pages") from None
                    break
                free.pop(0)
                self.queue.popleft()
                picks.append((i, req))
            if not picks:
                return
        else:
            while free and self.queue:
                picks.append((free.pop(0), self.queue.popleft()))
        now = time.perf_counter()
        for i, req in picks:
            self.slots[i] = _Slot(
                req=req, key=req.key, emitted=[], last_token=0,
                admit_step=self.step_count,
                submitted_s=self._submitted_s.pop(req.uid), admitted_s=now)
        self.stats["admitted"] += len(picks)
        # Chunked ingestion (DESIGN.md §12): a pick whose un-resident
        # suffix exceeds the chunk does NOT prefill here — its slot
        # enters `ingesting` and `_ingest_tick` streams the prompt in
        # one chunk per tick, alongside the pool's decode.  Short picks
        # keep the single-shot path (their bucketed widths are <= chunk).
        if self.chunk is not None:
            short: list[tuple[int, Request]] = []
            for i, req in picks:
                n = int(np.asarray(req.prompt).size)
                if n - hists.get(i, 0) > self.chunk:
                    self.slots[i].ingest_pos = hists.get(i, 0)
                    self.slots[i].ingesting = True
                else:
                    short.append((i, req))
            picks = short
        # Bucket the admit group by shared-history page count: one
        # prefill call per distinct hist_pages, each at ITS OWN group-max
        # suffix width.  A mixed-history group no longer pays the widest
        # suffix for every slot (the PR 6 width bug): a prefix-cache hit
        # whose suffix is 3 tokens prefills at width 3 even when a fresh
        # 40-token prompt admits in the same tick.
        buckets: dict[int, list[tuple[int, Request]]] = {}
        for i, req in picks:
            hp = hists.get(i, 0) // self.scfg.page_size \
                if self.paged is not None else 0
            buckets.setdefault(hp, []).append((i, req))
        for hp in sorted(buckets):
            self._prefill_group(buckets[hp], hists, hp, finished)
        if self.paged is not None:
            self.stats["shared_prefix_tokens"] = self.paged.shared_tokens
        if self.spec_k and picks:
            self._draft_prefill(picks)

    def _width(self, n: int) -> int:
        """The prefill width of `n` tokens: rounded up to
        `prefill_bucket`, capped at max_seq."""
        return min(-(-n // self.prefill_bucket) * self.prefill_bucket,
                   self.scfg.max_seq)

    def _prefill_group(self, picks: list[tuple[int, Request]],
                       hists: dict[int, int], hist_pages: int,
                       finished: list[Completion]) -> None:
        """Prefill `picks` (all sharing `hist_pages` resident history
        pages); each admitted slot's first output token comes from its
        last-token logits row (same semantics as serve.generate)."""
        if self.paged is None:
            self._prefill_slots(picks, finished)
            return
        b = self.scfg.batch
        # with a prefix-cache hit only the un-resident suffix prefills
        width = self._width(max(int(np.asarray(r.prompt).size)
                                - hists.get(i, 0) for i, r in picks))
        with TraceAnnotation("serve.prefill", width=width, picks=len(picks),
                             rows=b * width):
            with TraceAnnotation("serve.stage"):
                tokens = np.zeros((b, width), np.int32)
                lengths = np.ones((b,), np.int32)
                mask = np.zeros((b,), bool)
                hist_arr = np.zeros((b,), np.int32)
                for i, req in picks:
                    prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                    suffix = prompt[hists.get(i, 0):]
                    tokens[i, : suffix.size] = suffix
                    lengths[i] = suffix.size
                    hist_arr[i] = hists.get(i, 0)
                    mask[i] = True
                with self._scope():
                    logits, self.cache = self._prefill(
                        self.params, jnp.asarray(tokens), self.cache,
                        jnp.asarray(lengths), jnp.asarray(mask),
                        jnp.asarray(self.paged.tables),
                        jnp.asarray(hist_arr), hist_pages=hist_pages)
            with TraceAnnotation("serve.pull"):
                rows = np.asarray(logits[:, -1], np.float32)
            self._count_prefill(width, int(lengths[mask].sum()), len(picks),
                                b * width)
            # index the now-resident full prompt pages so later
            # admissions with the same prefix reuse them (ingesting
            # slots defer to their final chunk — the index must not
            # advertise pages whose rows are not written yet)
            for i, req in picks:
                self.paged.note_prefilled(
                    i, np.asarray(req.prompt, np.int32).tolist())
            with TraceAnnotation("serve.sample"):
                for i, _ in picks:
                    self._emit(i, self._sample(self.slots[i], rows[i]),
                               finished)

    def _prefill_slots(self, picks: list[tuple[int, Request]],
                       finished: list[Completion]) -> None:
        """Contiguous layout: one batch-1 prefill per pick over its own
        slot, at the pick's own bucketed width, so no row of another
        slot and no wider prompt of the group is computed.  Every call
        is dispatched (two in flight at most) before the one pull of
        the picks' last-token rows, which sits with the sampling in the
        last call's span."""
        outs = []
        for n, (i, req) in enumerate(picks):
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            width = self._width(prompt.size)
            with TraceAnnotation("serve.prefill", width=width, picks=1,
                                 rows=width):
                with TraceAnnotation("serve.stage"):
                    tokens = np.zeros((1, width), np.int32)
                    tokens[0, : prompt.size] = prompt
                    if n >= 2:
                        # two calls in flight at most: each holds a new
                        # whole cache from its dispatch on, so a tick's
                        # every pick at once would hold one per pick
                        outs[n - 2].block_until_ready()
                    with self._scope():
                        logits, self.cache = self._prefill(
                            self.params, jnp.asarray(tokens), self.cache,
                            jnp.asarray([prompt.size], np.int32),
                            np.int32(i))
                outs.append(logits)
                self._count_prefill(width, prompt.size, 1, width)
                if n < len(picks) - 1:
                    continue
                with TraceAnnotation("serve.pull"):
                    # (1, 1, V) each: the host converts, no device op
                    rows = [np.asarray(x, np.float32)[0, -1]
                            for x in jax.device_get(outs)]
                with TraceAnnotation("serve.sample"):
                    for (j, _), row in zip(picks, rows, strict=True):
                        self._emit(j, self._sample(self.slots[j], row),
                                   finished)

    def _count_prefill(self, width: int, tokens: int, slots: int,
                       rows: int) -> None:
        st = self.stats
        st["prefill_calls"] += 1
        st["prefill_widths"].add(width)
        st["prefill_tokens"] += tokens
        st["prefill_width_sum"] += width * slots
        st["prefill_rows"] += rows

    def _ingest_tick(self, finished: list[Completion]) -> None:
        """Advance every ingesting slot by one `prefill_chunk`-wide
        chunk (DESIGN.md §12).  One fused call covers all ingesting
        slots — `hist_len` is a traced array, so slots at different
        depths (including a first chunk at hist 0) share the trace.  On
        the paged layout slots are grouped by resident page count
        (`hist_pages` is a static arg) and the shallowest group goes
        first: deeper slots wait a tick, bounding retraces exactly like
        the shared-prefix admit buckets.  A slot whose prompt completes
        this tick leaves `ingesting`, registers its prefix pages, emits
        its first output token from the chunk logits, and (when
        speculating) replays its full prompt through the draft cache —
        all the steps the single-shot admit runs, just deferred to the
        final chunk."""
        ing = [(i, s) for i, s in enumerate(self.slots)
               if s is not None and s.ingesting]
        if not ing:
            return
        if self.paged is not None:
            groups: dict[int, list[tuple[int, _Slot]]] = {}
            for i, s in ing:
                groups.setdefault(
                    s.ingest_pos // self.scfg.page_size, []).append((i, s))
            hp = min(groups)
            ing = groups[hp]
        else:
            hp = 0
        b, ch = self.scfg.batch, self.chunk
        with TraceAnnotation("serve.ingest", width=ch, slots=len(ing),
                             rows=b * ch):
            with TraceAnnotation("serve.stage"):
                tokens = np.zeros((b, ch), np.int32)
                lengths = np.ones((b,), np.int32)
                mask = np.zeros((b,), bool)
                hist_arr = np.zeros((b,), np.int32)
                takes: dict[int, int] = {}
                for i, s in ing:
                    prompt = np.asarray(s.req.prompt, np.int32).reshape(-1)
                    take = min(ch, prompt.size - s.ingest_pos)
                    tokens[i, :take] = prompt[s.ingest_pos:
                                              s.ingest_pos + take]
                    lengths[i] = take
                    hist_arr[i] = s.ingest_pos
                    mask[i] = True
                    takes[i] = take
                with self._scope():
                    if self.paged is not None:
                        logits, self.cache = self._chunk_prefill(
                            self.params, jnp.asarray(tokens), self.cache,
                            jnp.asarray(lengths), jnp.asarray(mask),
                            jnp.asarray(self.paged.tables),
                            jnp.asarray(hist_arr), hist_pages=hp)
                    else:
                        logits, self.cache = self._chunk_prefill(
                            self.params, jnp.asarray(tokens), self.cache,
                            jnp.asarray(lengths), jnp.asarray(mask),
                            jnp.asarray(hist_arr))
            with TraceAnnotation("serve.pull"):
                rows = np.asarray(logits[:, -1], np.float32)
            self._count_prefill(ch, sum(takes.values()), len(ing), b * ch)
            done: list[tuple[int, Request]] = []
            for i, s in ing:
                s.ingest_pos += takes[i]
                if s.ingest_pos >= int(np.asarray(s.req.prompt).size):
                    s.ingesting = False
                    done.append((i, s.req))
            if not done:
                return
            if self.paged is not None:
                for i, req in done:
                    self.paged.note_prefilled(
                        i, np.asarray(req.prompt, np.int32).tolist())
                self.stats["shared_prefix_tokens"] = self.paged.shared_tokens
            with TraceAnnotation("serve.sample"):
                for i, _ in done:
                    self._emit(i, self._sample(self.slots[i], rows[i]),
                               finished)
            if self.spec_k:
                self._draft_prefill(done)

    def _draft_prefill(self, picks: list[tuple[int, Request]]) -> None:
        """Prefill the draft cache with the FULL prompts of the slots
        just admitted (the draft shares no prefixes — its cache is
        private and contiguous).  The logits are discarded: the first
        emitted token comes from the TARGET's prefill row, and the next
        spec tick feeds it back through `draft_propose`."""
        b = self.scfg.batch
        width = self._width(max(int(np.asarray(r.prompt).size)
                                for _, r in picks))
        with TraceAnnotation("serve.draft_prefill", width=width,
                             picks=len(picks)), \
                TraceAnnotation("serve.stage"):
            tokens = np.zeros((b, width), np.int32)
            lengths = np.ones((b,), np.int32)
            mask = np.zeros((b,), bool)
            for i, req in picks:
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                tokens[i, : prompt.size] = prompt
                lengths[i] = prompt.size
                mask[i] = True
            with self._scope():
                _, self.draft_cache = self._dprefill(
                    self.draft_params, jnp.asarray(tokens), self.draft_cache,
                    jnp.asarray(lengths), jnp.asarray(mask))

    def _clocks(self, active: np.ndarray) -> dict[int, int]:
        """Each active slot's clock: its resident rows, and the position
        its next token is written at (prompt_len + emitted - 1; the
        first emitted token came from prefill, not decode)."""
        return {i: int(np.asarray(s.req.prompt).size) + len(s.emitted) - 1
                for i, s in enumerate(self.slots) if active[i]}

    def _decode_active(self, finished: list[Completion]) -> None:
        # ingesting slots sit decode out: their prompt is still streaming
        # in and they have no token to feed back yet (DESIGN.md §12)
        active = np.asarray(
            [s is not None and not s.ingesting for s in self.slots])
        if not active.any():
            return
        n_active = int(active.sum())
        with TraceAnnotation("serve.decode", slots=n_active):
            with TraceAnnotation("serve.stage"):
                toks = np.asarray(
                    [s.last_token if s is not None else 0
                     for s in self.slots], np.int32)[:, None]
                clocks = self._clocks(active)
                if self.paged is not None:
                    # make each active slot's write-frontier page exist
                    # (and be private — asserted) before the fused step
                    # writes it
                    for i, pos in clocks.items():
                        self.paged.ensure_decode_page(i, pos)
                with self._scope():
                    if self.paged is not None:
                        logits, self.cache = self._decode(
                            self.params, self.cache, jnp.asarray(toks),
                            jnp.asarray(active),
                            jnp.asarray(self.paged.tables))
                    else:
                        logits, self.cache = self._decode(
                            self.params, self.cache, jnp.asarray(toks),
                            jnp.asarray(active))
            with TraceAnnotation("serve.pull"):
                rows = np.asarray(logits[:, -1], np.float32)
            self.stats["decode_steps"] += 1
            self.stats["decode_tokens"] += n_active
            self.stats["decode_slots"] += n_active
            self.stats["decode_kv_rows"] += sum(clocks.values())
            with TraceAnnotation("serve.sample"):
                for i in range(len(self.slots)):
                    if active[i]:
                        self._emit(i, self._sample(self.slots[i], rows[i]),
                                   finished)

    def _spec_tick(self, finished: list[Completion]) -> None:
        """One speculative tick (DESIGN.md §9): draft k tokens, verify
        all k+1 positions in one fused pass, emit each slot's accepted
        prefix plus the target's correction token, resync the draft.
        Three dispatches replace the k+1 sequential decode steps the
        same tokens would otherwise cost."""
        active = np.asarray(
            [s is not None and not s.ingesting for s in self.slots])
        if not active.any():
            return
        k = self.spec_k
        n_active = int(active.sum())
        with TraceAnnotation("serve.spec", slots=n_active):
            with TraceAnnotation("serve.stage"):
                last = np.asarray(
                    [s.last_token if s is not None else 0
                     for s in self.slots], np.int32)
                clocks = self._clocks(active)
                if self.paged is not None:
                    # the verify writes span pos..pos+k: make every page
                    # on the span exist (and be private) before the
                    # fused pass
                    page = self.paged.page
                    for i, pos in clocks.items():
                        for pg in range(pos // page, (pos + k) // page + 1):
                            self.paged.ensure_decode_page(
                                i, max(pos, pg * page))
                act = jnp.asarray(active)
                with self._scope():
                    drafts = self._propose(self.draft_params,
                                           self.draft_cache,
                                           jnp.asarray(last), act)
                    toks = jnp.concatenate(
                        [jnp.asarray(last)[:, None], drafts], axis=1)
                    if self.paged is not None:
                        g, n_acc, self.cache = self._verify(
                            self.params, self.cache, toks, act,
                            jnp.asarray(self.paged.tables))
                    else:
                        g, n_acc, self.cache = self._verify(
                            self.params, self.cache, toks, act)
                    self.draft_cache = self._advance(
                        self.draft_params, self.draft_cache, toks,
                        n_acc + 1, act)
            with TraceAnnotation("serve.pull"):
                g_np = np.asarray(g)
                acc_np = np.asarray(n_acc)
            self.stats["decode_steps"] += 1
            self.stats["spec_ticks"] += 1
            self.stats["draft_tokens"] += k * n_active
            self.stats["accepted_draft_tokens"] += int(acc_np[active].sum())
            self.stats["decode_slots"] += n_active
            self.stats["decode_kv_rows"] += sum(clocks.values())
            with TraceAnnotation("serve.sample"):
                for i, t0 in clocks.items():
                    # t0: committed write frontier BEFORE this tick's
                    # emissions
                    for j in range(int(acc_np[i]) + 1):
                        if self.slots[i] is None:  # EOS/budget mid-window
                            break
                        self._emit(i, int(g_np[i, j]), finished)
                        self.stats["decode_tokens"] += 1
                    if self.paged is not None and self.slots[i] is not None:
                        # clock-decrement rollback happened in-graph;
                        # release any page now holding only rejected
                        # rows.  The last committed row is t0 + n_acc
                        # (keep = n_acc + 1 rows starting at t0).
                        self.paged.rollback(i, t0 + int(acc_np[i]))

    # -- driver ------------------------------------------------------------

    def step(self) -> list[Completion]:
        """One scheduler tick: admit into free slots, advance chunked
        ingestion, then one fused decode (or draft/verify/resync, when
        speculating) over the pool.  Returns requests finished this
        tick."""
        finished: list[Completion] = []
        with TraceAnnotation("serve.step"):
            self._admit(finished)
            if self.chunk is not None:
                self._ingest_tick(finished)
            if self.spec_k:
                self._spec_tick(finished)
            else:
                self._decode_active(finished)
        self.step_count += 1
        return finished

    def run(self, requests=(), *, max_steps: int | None = None
            ) -> dict[int, Completion]:
        """Submit `requests`, drive until queue and pool drain, and
        return {uid: Completion}."""
        for r in requests:
            self.submit(r)
        steps = 0
        while self.queue or self.n_active:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"scheduler did not drain in {max_steps} steps "
                    f"({self.n_active} active, {len(self.queue)} queued)")
        return self.completions

    def serve_async(self, *, max_queue: int = 0,
                    start: bool = True) -> "AsyncServer":
        """Wrap this scheduler in the async ingestion plane (DESIGN.md
        §12): a worker thread drives the tick loop, callers submit
        through a bounded queue and get a Future per request.  The
        scheduler must not be stepped directly while the server is
        running — the worker owns it."""
        return AsyncServer(self, max_queue=max_queue, start=start)


class AsyncServer:
    """Async ingestion plane over a `Scheduler` (DESIGN.md §12).

    One worker thread owns the scheduler: it drains the submission
    queue into `Scheduler.submit` and drives `step()` while there is
    work, blocking on the queue when idle — the jitted step never runs
    concurrently with itself, so no lock guards the cache.  Callers
    touch only the queue and the returned futures:

        with sched.serve_async(max_queue=32) as srv:
            futs = [srv.submit(r) for r in requests]
            outs = [f.result() for f in futs]

    Backpressure: with `max_queue > 0`, `submit` blocks while the queue
    is full (bounding the submission rate to the service rate); pass
    `timeout=` to get `queue.Full` instead of blocking.  Requests the
    scheduler rejects (validation errors) surface on the request's
    Future, not in the worker.  `shutdown()` stops intake, lets the
    worker drain everything already submitted, and joins it."""

    _IDLE_POLL = 0.05  # seconds the idle worker blocks per queue wait

    def __init__(self, sched: Scheduler, *, max_queue: int = 0,
                 start: bool = True):
        if max_queue < 0:
            raise ValueError(f"max_queue must be >= 0: {max_queue}")
        self._sched = sched
        self._q: queue.Queue = queue.Queue(maxsize=max_queue)
        self._futures: dict[int, concurrent.futures.Future] = {}
        self._stop = threading.Event()
        self._started = False
        self._thread = threading.Thread(
            target=self._worker, name="serve-async-worker", daemon=True)
        if start:
            self.start()

    def start(self) -> None:
        if not self._started:
            self._started = True
            self._thread.start()

    def submit(self, req: Request,
               timeout: float | None = None) -> concurrent.futures.Future:
        """Queue `req`; returns a Future resolving to its Completion.
        Blocks while the bounded queue is full (backpressure); with
        `timeout=` raises `queue.Full` instead.  Raises RuntimeError
        after `shutdown`."""
        if self._stop.is_set():
            raise RuntimeError("submit after shutdown")
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._q.put((req, fut), timeout=timeout)
        return fut

    def shutdown(self, wait: bool = True) -> None:
        """Stop intake; the worker drains every request already queued
        or in flight, then exits.  `wait=True` joins it."""
        self._stop.set()
        if wait and self._started:
            self._thread.join()

    def __enter__(self) -> "AsyncServer":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- worker side -------------------------------------------------------

    def _intake(self, item) -> None:
        req, fut = item
        try:
            self._sched.submit(req)
        except Exception as e:  # validation error -> the caller's future
            fut.set_exception(e)
            return
        self._futures[req.uid] = fut

    def _drain_submissions(self) -> None:
        while True:
            try:
                self._intake(self._q.get_nowait())
            except queue.Empty:
                return

    def _worker(self) -> None:
        sched = self._sched
        while True:
            self._drain_submissions()
            if sched.queue or sched.n_active:
                for comp in sched.step():
                    fut = self._futures.pop(comp.uid, None)
                    if fut is not None:
                        fut.set_result(comp)
            elif self._stop.is_set() and self._q.empty():
                return
            else:  # idle: block on the queue instead of spinning
                try:
                    self._intake(self._q.get(timeout=self._IDLE_POLL))
                except queue.Empty:
                    pass
