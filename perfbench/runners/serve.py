"""The serving runner: one cell's traffic through the program's
continuous-batching `Scheduler`, timed on the host clock.

Set-up makes the weights from `--seed` on the device, warms every
prefill width the mix can use and the decode step, then starts the
traffic.  Requests due in the first `lead_s` seconds bring the pool to
steady occupancy and are served but not measured; the window's requests
are those due in the next `--seconds`.  Arrivals go on during the drain
that follows, so the last window requests see the same load; the drain
ends when every window request has finished, or at `drain_cap_s`.

Every time is taken from the moment a request was due, so a stall of
the loop delays the requests behind it.  A token's time is the end of
the tick that produced it: the scheduler has pulled it to the host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import time

import numpy as np

import stalls
import traffic
import work
from harness import log


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it (times in perf_counter s)."""
    idx: int
    prompt_len: int
    max_new: int
    due: float
    admit: float = float("nan")     # start of the tick that admitted it
    times: list = dataclasses.field(default_factory=list)
    in_window: bool = False
    slot: int = -1                  # pool slot that served it

    @property
    def done(self) -> bool:
        return len(self.times) >= self.max_new


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    prefill_lens: list       # prompt lengths admitted in this tick
    decode_ctxs: list        # live rows of each slot decoded
    cpu: float = 0.0         # thread CPU time inside the tick
    dispatch: float = 0.0    # time in the prefill and decode calls


class CompileCounter:
    """Counts jaxpr traces and backend compiles from JAX's own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.n += 1


def _named_steps(sched):
    """The scheduler's prefill and decode programs under stable names,
    so the trace groups device time by them (`bench.prefill`,
    `bench.decode`).  The inner jits are inlined: the computation is the
    program's own."""
    import jax

    inner_prefill, inner_decode = sched._prefill, sched._decode

    def bench_prefill(*a):
        return inner_prefill(*a)

    def bench_decode(*a):
        return inner_decode(*a)

    return jax.jit(bench_prefill), jax.jit(bench_decode)


def serve_options(srv: dict, max_seq: int):
    """The traffic file's `serve` block as the program's `ServeConfig`
    and the `Scheduler`'s keyword arguments: a key of either passes
    through as given, any other key is an error.  `max_seq`, unless the
    block gives it, is the mix's longest prompt plus output."""
    import inspect

    from repro.serve_lib.scheduler import Scheduler
    from repro.serve_lib.serve import ServeConfig

    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    sched_kw = {n for n, p in inspect.signature(Scheduler).parameters.items()
                if p.kind is p.KEYWORD_ONLY}
    unknown = sorted(set(srv) - fields - sched_kw)
    if unknown:
        raise SystemExit(f"perfbench: unknown serve keys {unknown}: neither "
                         f"ServeConfig fields nor Scheduler arguments")
    scfg = ServeConfig(**{"max_seq": max_seq,
                          **{k: v for k, v in srv.items() if k in fields}})
    return scfg, {k: v for k, v in srv.items() if k in sched_kw}


class Runner:
    def __init__(self, h):
        self.h = h                      # harness context
        self.spec = h.traffic
        self.shapes = work.Shapes.from_config(h.conf)
        self.requests: list[Rec] = []
        self.ticks: list[Tick] = []
        self.trace = None               # reduced trace, when traced
        self.trace_window = None        # (t0, t1) perf_counter s
        self.compiles_in_window = 0
        self.lateness: list[float] = []
        self._dispatch = 0.0

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import transformer as T
        from repro.serve_lib.scheduler import Request, Scheduler

        h, spec = self.h, self.spec
        log(f"setup: imports and device ready at "
            f"{time.perf_counter() - h.t_process:.3f} s")
        self.compiles = CompileCounter()
        self.cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.cfg = h.cfgmod.program_config(h.conf)
        self.scfg, self.sched_kw = serve_options(spec["serve"],
                                                 traffic.max_seq(spec))
        self.Scheduler = Scheduler
        self.params = jax.block_until_ready(
            h.cfgmod.init_weights(h.conf, h.seed))
        want = jax.eval_shape(lambda k: T.init_params(k, self.cfg),
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), self.params)
        exp = jax.tree.map(lambda x: (x.shape, "bfloat16"), want)
        if jax.tree.structure(got) != jax.tree.structure(exp) or \
                jax.tree.leaves(got) != jax.tree.leaves(exp):
            raise RuntimeError("benchmark weights do not match the "
                               "program's parameter layout")
        log(f"setup: weights made at {time.perf_counter() - h.t_process:.3f} s")
        self.Request = Request
        # warm every admit width the mix can produce, and decode
        warm = self.make_scheduler()
        for k, w in enumerate(traffic.prefill_widths(spec)):
            warm.submit(Request(uid=-1 - k, prompt=np.zeros(w, np.int32),
                                max_new_tokens=2))
            while warm.queue or warm.n_active:
                warm.step()
        del warm
        gc.collect()
        log(f"setup: {len(traffic.prefill_widths(spec))} prefill widths and "
            f"decode warm at {time.perf_counter() - h.t_process:.3f} s "
            f"({self.compiles.n} traces and compiles)")
        self.items = traffic.schedule(
            spec, spec["lead_s"] + h.seconds + spec["drain_cap_s"])
        # token ids come from the tokenizer's range, which a padded
        # embedding table may exceed
        self.prompts = {
            it.idx: traffic.prompt_tokens(h.seed, it.idx, it.prompt_len,
                                          self.shapes.token_ids)
            for it in self.items}

    def make_scheduler(self):
        """A fresh `Scheduler` whose prefill and decode programs run
        under the benchmark's stable names, timed."""
        s = self.Scheduler(self.params, self.cfg, self.scfg, **self.sched_kw)
        if not hasattr(self, "_steps"):
            self._steps = _named_steps(s)
        s._prefill, s._decode = (self._timed(f) for f in self._steps)
        return s

    def _timed(self, fn):
        def call(*a):
            t = time.perf_counter()
            try:
                return fn(*a)
            finally:
                self._dispatch += time.perf_counter() - t
        return call

    # -- the measured traffic ----------------------------------------------------

    def run(self) -> None:
        """Serve the traffic: lead-in, the window, then the drain."""
        import jax

        h, spec = self.h, self.spec
        sched = self.make_scheduler()
        closed = spec["arrivals"] == "closed"
        backlog = int(spec.get("backlog", 0))
        clock = time.perf_counter
        t0 = clock()
        ws = t0 + spec["lead_s"]
        we = ws + h.seconds
        cap = we + spec["drain_cap_s"]
        # trace the window's last seconds
        trace_at = we - min(h.seconds, spec.get("trace_s", h.seconds))
        self.ws, self.we = ws, we
        self.trace_window = None
        span = (jax.profiler.TraceAnnotation if h.trace
                else contextlib.nullcontext)
        recs: dict[int, Rec] = {}
        self.gc_pauses = []
        gc_start = []

        def on_gc(phase, info):
            if phase == "start":
                gc_start.append(clock())
            elif gc_start:
                self.gc_pauses.append(clock() - gc_start.pop())

        gc.callbacks.append(on_gc)
        watch = stalls.Watch()
        try:
            with watch:
                self._loop(sched, recs, span, watch, t0, ws, we, cap,
                           trace_at, closed, backlog)
        finally:
            gc.callbacks.remove(on_gc)
        if closed:
            for r in recs.values():
                r.in_window = bool(r.times) and r.admit < we and \
                    r.times[-1] >= ws
        self.requests = sorted(recs.values(), key=lambda r: r.idx)
        self.stats = dict(sched.stats)
        self.completions = {u: c.tokens
                            for u, c in sched.completions.items()}
        self.sched = sched
        self._log_stalls()
        for line in watch.report(ws):
            log(line)

    def _loop(self, sched, recs, span, watch, t0, ws, we, cap, trace_at,
              closed, backlog) -> None:
        import jax

        h, clock = self.h, time.perf_counter
        nxt = 0
        tracing = False
        compiles0 = None
        while True:
            now = clock()
            watch.beat = now
            if h.trace and not tracing and self.trace_window is None \
                    and now >= trace_at:
                tracing = self._start_trace(now)
            if tracing and now >= we:
                jax.profiler.stop_trace()
                tracing = False
                self.trace_window = (self.trace_t0, now)
                log(f"trace: stopped in {clock() - now:.3f} s")
            if compiles0 is None and now >= ws:
                compiles0 = self.compiles.n
            if compiles0 is not None and now < we:
                self.compiles_in_window = self.compiles.n - compiles0
            with span("bench.submit"):
                nxt = self._submit_due(sched, recs, nxt, now, t0, ws,
                                       we, closed, backlog)
            if now >= we:
                pending = [r for r in recs.values()
                           if (r.in_window or closed) and not r.done]
                if not pending or now >= cap:
                    break
            if not (sched.queue or sched.n_active):
                if not closed and nxt < len(self.items):
                    time.sleep(max(0.0, min(
                        0.002, t0 + self.items[nxt].due_s - clock())))
                continue
            self._tick(sched, recs, span)

    def _start_trace(self, now: float) -> bool:
        import jax

        out = self.h.out_dir / "trace"
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(out), profiler_options=opts)
        self.trace_t0 = time.perf_counter()
        log(f"trace: started in {self.trace_t0 - now:.3f} s")
        return True

    def _submit_due(self, sched, recs, nxt, now, t0, ws, we, closed,
                    backlog) -> int:
        """Submit every request due by `now` (closed loop: top the
        queue up to `backlog` until the window ends)."""
        while nxt < len(self.items):
            it = self.items[nxt]
            if closed:
                if now >= we or len(sched.queue) >= backlog:
                    break
                due = now
            else:
                due = t0 + it.due_s
                if due > now:
                    break
            recs[it.idx] = Rec(it.idx, it.prompt_len, it.max_new, due,
                               in_window=ws <= due < we)
            sched.submit(self.Request(
                uid=it.idx, prompt=self.prompts[it.idx],
                max_new_tokens=it.max_new, eos_id=None))
            self.lateness.append(now - due)
            nxt += 1
        if closed and nxt >= len(self.items) and now < we:
            raise RuntimeError("closed backlog ran dry inside the window: "
                               "raise max_requests")
        return nxt

    def _log_stalls(self) -> None:
        """The run's longest ticks and longest gaps between ticks, each
        with its time from the window's start, and the interpreter's
        garbage-collection pauses."""
        ws = self.ws
        longest = sorted(self.ticks, key=lambda t: t.start - t.end)[:4]
        gaps = sorted(((b.start - a.end, a.end - ws) for a, b in
                       zip(self.ticks, self.ticks[1:])), reverse=True)[:3]
        late = max(((r.admit - r.due if r.admit == r.admit else 0, r.due - ws)
                    for r in self.requests), default=(0, 0))
        log("loop: longest ticks " + ", ".join(
            f"{(t.end - t.start) * 1e3:.1f} ms at {t.start - ws:.2f} s "
            f"(cpu {t.cpu * 1e3:.1f} ms, in calls {t.dispatch * 1e3:.1f} "
            f"ms, prefill {sum(t.prefill_lens)} tokens)" for t in longest)
            + "; longest gaps between ticks " + ", ".join(
                f"{g * 1e3:.1f} ms at {at:.2f} s" for g, at in gaps)
            + f"; longest wait to admission {late[0] * 1e3:.1f} ms for a "
            f"request due at {late[1]:.2f} s"
            + f"; gc pauses {len(self.gc_pauses)}, longest "
            f"{max(self.gc_pauses, default=0) * 1e3:.1f} ms")

    def _tick(self, sched, recs, span) -> None:
        clock = time.perf_counter
        before = {s.req.uid: len(s.emitted) for s in sched.slots
                  if s is not None}
        queued = {r.uid for r in sched.queue}
        dec0 = sched.stats["decode_tokens"]
        self._dispatch = 0.0
        cpu0 = time.thread_time()
        start = clock()
        with span("bench.step"):
            finished = sched.step()
        end = clock()
        cpu = time.thread_time() - cpu0
        after = {s.req.uid: len(s.emitted) for s in sched.slots
                 if s is not None}
        after.update({c.uid: len(c.tokens) for c in finished})
        admitted = queued - {r.uid for r in sched.queue}
        ctxs = [recs[u].prompt_len + n for u, n in before.items()]
        ctxs += [recs[u].prompt_len + 1 for u in admitted
                 if after.get(u, 0) >= 2]
        if sched.stats["decode_tokens"] - dec0 != len(ctxs):
            raise RuntimeError("decode accounting disagrees with the "
                               "scheduler's counter")
        for i, s in enumerate(sched.slots):
            if s is not None and s.req.uid in admitted:
                recs[s.req.uid].slot = i
        for u in admitted:
            recs[u].admit = start
        for u, n in after.items():
            r = recs[u]
            r.times.extend([end] * (n - len(r.times)))
        self.ticks.append(Tick(start, end,
                               [recs[u].prompt_len for u in admitted], ctxs,
                               cpu, self._dispatch))

    # -- after the window --------------------------------------------------------

    def finish_trace(self) -> None:
        import tracing
        t0 = time.perf_counter()
        self.trace = tracing.load(str(self.h.out_dir / "trace"))
        log(f"trace: read in {time.perf_counter() - t0:.3f} s")

    def summary(self) -> tuple[int, int]:
        """(requests due in the window, those unfinished at the drain's
        cap), with the run's own lines on standard error."""
        window = [r for r in self.requests if r.in_window]
        failed = sum(not r.done for r in window)
        lat = np.asarray(self.lateness) * 1e3
        log(f"window: {len(window)} requests, {failed} unfinished, "
            f"{sum(len(r.times) for r in window)} tokens; generator "
            f"late p50 {np.median(lat):.3f} ms, max {lat.max():.3f} ms; "
            f"compiles in window {self.compiles_in_window}")
        log(f"scheduler stats: {self.stats}")
        return len(window), failed

    def free(self) -> None:
        del self.sched
        gc.collect()

    def check(self) -> dict:
        """Compare what the window served with the plain float32
        reference: a sample of finished window requests drawn from the
        seed, the longest among them, each prompt with its served tokens
        run once.  The number is the widest gap by which a served
        (greedy) token's reference logit lies below the reference's best
        at that position.  With `--control 1` the reference's first
        choices in float8 take the served tokens' place in that number,
        and the served tokens' own gap is only printed."""
        import jax.numpy as jnp

        h = self.h
        spec = h.traffic["check"]
        done = [r for r in self.requests if r.in_window and r.done]
        if not done:
            return {"finished_requests_missing": {"value": 1, "limit": 0}}
        sample = [max(done, key=lambda r: (r.prompt_len + r.max_new, r.idx))]
        # then one request served in each slot of the pool, drawn from
        # the seed, so a fault confined to some slots cannot hide
        rng = np.random.default_rng([h.seed, 0xC4EC])
        for slot in sorted({r.slot for r in done}):
            cands = [r for r in done if r.slot == slot and r not in sample]
            if cands and len(sample) < spec["requests"]:
                sample.append(cands[rng.integers(len(cands))])
        served_gaps, control_gaps, bad, n_tok = [], [], 0, 0
        t0 = time.perf_counter()
        vocab = self.shapes.vocab
        width = h.traffic["output"]["max"]
        for r in sample:
            served = np.asarray(self.completions[r.idx], np.int32)
            if served.size != r.max_new or served.min() < 0 or \
                    served.max() >= vocab:
                bad += 1
                continue
            prompt = self.prompts[r.idx]
            seq = np.concatenate([prompt, served[:-1]])
            bucket = spec["pad_to"]
            padded = np.zeros(-(-seq.size // bucket) * bucket, np.int32)
            padded[:seq.size] = seq
            # the rows that produced each served token, padded to one
            # length by repeating the last (a repeat cannot widen a
            # maximum)
            rows = np.arange(prompt.size - 1, seq.size, dtype=np.int32)
            rows = np.concatenate([rows, np.full(width - rows.size, rows[-1])])
            toks = np.concatenate([served,
                                   np.full(width - served.size, served[-1])])
            g = h.cfgmod.gaps(h.conf, self.params, jnp.asarray(padded),
                              jnp.asarray(rows), jnp.asarray(toks),
                              control=h.control)
            served_gaps.append(float(g["served"]))
            if h.control:
                control_gaps.append(float(g["control"]))
            n_tok += served.size
        log(f"reference: {len(sample)} requests, {n_tok} served tokens "
            f"compared in {time.perf_counter() - t0:.2f} s")
        gap = max(served_gaps, default=float("inf"))
        if h.control:
            log(f"control: served tokens' widest gap {gap!r}; the fp8 "
                f"reference's first choices are compared in their place")
            gap = max(control_gaps, default=float("inf"))
        return {"max_logit_gap": {"value": gap,
                                  "limit": h.limits["max_logit_gap"]},
                "bad_requests": {"value": bad, "limit": 0}}
