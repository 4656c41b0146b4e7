"""Continuous-batching scheduler: ragged parity, slot lifecycle, clocks.

The four cache kinds are covered through their serving archs:
  qwen2-1.5b        full attention
  mixtral-8x7b      sliding-window ring cache (+ MoE)
  mamba2-780m       SSM (conv + SSD state)
  recurrentgemma-2b RG-LRU (+ local ring)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as engine_mod
from repro.configs import get_config
from repro.models import transformer as T
from repro.serve_lib import serve as serve_lib
from repro.serve_lib.scheduler import Request, Scheduler

KINDS = ["qwen2-1.5b", "mixtral-8x7b", "mamba2-780m", "recurrentgemma-2b"]

CHUNK = 8  # > one page / bucket, small enough that smoke prompts span it


def _cfg(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:  # avoid capacity drops in exactness checks
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _setup(arch, batch, max_seq=48):
    cfg = _cfg(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    scfg = serve_lib.ServeConfig(max_seq=max_seq, batch=batch,
                                 compute_dtype=jnp.float32,
                                 cache_dtype=jnp.float32)
    return cfg, params, scfg


def _requests(cfg, n, rng, max_prompt=20, max_gen=8):
    reqs = []
    for uid in range(n):
        plen = int(rng.integers(3, max_prompt))
        gen = int(rng.integers(2, max_gen + 1))
        prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
        reqs.append(Request(uid=uid, prompt=prompt, max_new_tokens=gen))
    return reqs


# --------------------------------------------------------------------------
# Parity: continuous batching == per-request generate (greedy)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("arch", KINDS)
def test_scheduler_matches_generate(arch):
    """Mixed-length prompts served continuously through a 2-slot pool
    emit exactly the tokens per-request `generate` produces."""
    cfg, params, scfg = _setup(arch, batch=2)
    rng = np.random.default_rng(0)
    reqs = _requests(cfg, 5, rng)
    sched = Scheduler(params, cfg, scfg)
    comps = sched.run(reqs, max_steps=300)
    assert sorted(comps) == [r.uid for r in reqs]

    scfg1 = dataclasses.replace(scfg, batch=1)
    for r in reqs:
        ref = serve_lib.generate(params, cfg, scfg1,
                                 jnp.asarray(r.prompt)[None],
                                 r.max_new_tokens)
        np.testing.assert_array_equal(
            comps[r.uid].tokens, np.asarray(ref)[0],
            err_msg=f"{arch} uid={r.uid}")
        assert comps[r.uid].finish_reason == "length"


def test_scheduler_bucketed_prefill_still_correct():
    """prefill_bucket > 1 pads admit widths; outputs stay identical
    (prompt padding is masked out of every cache kind)."""
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    rng = np.random.default_rng(1)
    reqs = _requests(cfg, 4, rng)
    a = Scheduler(params, cfg, scfg).run(reqs, max_steps=300)
    reqs2 = [dataclasses.replace(r) for r in reqs]
    b = Scheduler(params, cfg, scfg, prefill_bucket=8).run(
        reqs2, max_steps=300)
    for uid in a:
        np.testing.assert_array_equal(a[uid].tokens, b[uid].tokens)


# --------------------------------------------------------------------------
# Slot lifecycle: eviction frees slots, freed slots readmit from queue
# --------------------------------------------------------------------------


def test_slot_eviction_and_readmission():
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    rng = np.random.default_rng(2)
    reqs = _requests(cfg, 6, rng, max_gen=5)
    sched = Scheduler(params, cfg, scfg)
    comps = sched.run(reqs, max_steps=300)
    assert sched.stats["admitted"] == 6
    assert sched.stats["finished"] == 6
    assert sched.n_active == 0 and not sched.queue
    # more requests than slots => freed slots were reused by later admits
    assert sched.stats["prefill_calls"] >= 2
    first_finish = min(c.finish_step for c in comps.values())
    late_admits = [c for c in comps.values() if c.admit_step > first_finish]
    assert late_admits, "no request was admitted into a freed slot"
    # decode compute tracks only live slots, never the whole pool blindly
    assert sched.stats["decode_tokens"] <= 2 * sched.stats["decode_steps"]


def test_eos_evicts_early():
    cfg, params, scfg = _setup("qwen2-1.5b", batch=1)
    prompt = np.arange(7, dtype=np.int32) % cfg.vocab
    free = Scheduler(params, cfg, scfg).run(
        [Request(uid=0, prompt=prompt, max_new_tokens=8)], max_steps=100)
    toks = free[0].tokens
    eos = int(toks[3])
    capped = Scheduler(params, cfg, scfg).run(
        [Request(uid=1, prompt=prompt, max_new_tokens=8, eos_id=eos)],
        max_steps=100)
    got = capped[1]
    assert got.finish_reason == "eos"
    assert got.tokens[-1] == eos
    assert len(got.tokens) <= 4
    np.testing.assert_array_equal(got.tokens, toks[: len(got.tokens)])


def test_scheduler_validations():
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2, max_seq=16)
    sched = Scheduler(params, cfg, scfg)
    ok = Request(uid=0, prompt=np.ones(4, np.int32), max_new_tokens=2)
    sched.submit(ok)
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(dataclasses.replace(ok))
    with pytest.raises(ValueError, match="max_seq"):
        sched.submit(Request(uid=1, prompt=np.ones(15, np.int32),
                             max_new_tokens=3))
    with pytest.raises(ValueError, match="PRNG key"):
        sched.submit(Request(uid=2, prompt=np.ones(3, np.int32),
                             max_new_tokens=2, temperature=0.5))
    with pytest.raises(ValueError, match="empty"):
        sched.submit(Request(uid=3, prompt=np.zeros(0, np.int32),
                             max_new_tokens=2))


def test_scheduler_temperature_runs():
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 5 + i, np.int64)
                    .astype(np.int32), max_new_tokens=3, temperature=1.0,
                    key=jax.random.PRNGKey(i))
            for i in range(3)]
    comps = Scheduler(params, cfg, scfg).run(reqs, max_steps=100)
    assert sorted(comps) == [0, 1, 2]
    assert all(len(c.tokens) == 3 for c in comps.values())


# --------------------------------------------------------------------------
# Per-kind cache clocks: ragged prefill state == exact per-request state
# --------------------------------------------------------------------------


def _slot_view(cache, i):
    """One slot's cache: slots leaves are (n_periods, B, ...), tail
    leaves (B, ...), the clock (B,)."""
    return {"t": cache["t"][i],
            "slots": jax.tree.map(lambda a: a[:, i], cache["slots"]),
            "tail": jax.tree.map(lambda a: a[i], cache["tail"])}


def _assert_slot_state_matches(cfg, view, ref, length):
    """Compare one ragged-prefill slot against an exact batch=1 prefill:
    recurrent leaves and ring contents exactly, attention rows [0, L)."""
    assert int(view["t"]) == int(ref["t"][0]) == length
    for j, kind in enumerate(cfg.layer_pattern):
        c, r = view["slots"][f"b{j}"], jax.tree.map(
            lambda a: a[:, 0], ref["slots"][f"b{j}"])
        if kind in ("attn", "local"):
            size = c["k"].shape[1]
            rows = size if length >= size else length
            for leaf in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(c[leaf][:, :rows]),
                    np.asarray(r[leaf][:, :rows]),
                    rtol=2e-5, atol=2e-5, err_msg=f"{kind}/{leaf}")
        else:
            for leaf in c:
                np.testing.assert_allclose(
                    np.asarray(c[leaf]), np.asarray(r[leaf]),
                    rtol=2e-5, atol=2e-5, err_msg=f"{kind}/{leaf}")


@pytest.mark.parametrize("arch", KINDS)
def test_ragged_prefill_state_per_kind(arch):
    """One padded ragged prefill writes, per slot, the same cache state
    (clock, attention rows, ring placement, conv/SSD/RG-LRU states) an
    exact-length per-request prefill produces.  Lengths straddle the
    smoke window (16) so rings wrap for one slot and not the other."""
    cfg, params, _ = _setup(arch, batch=3)
    lens = np.array([9, 24, 17], np.int32)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab, (3, 24)).astype(np.int32)
    for i, ln in enumerate(lens):
        toks[i, ln:] = 0
    cache = T.init_cache(cfg, T.CacheSpec(max_seq=40, batch=3),
                         dtype=jnp.float32)
    lg, cache_r = T.prefill(params, cfg, jnp.asarray(toks), cache,
                            compute_dtype=jnp.float32,
                            lengths=jnp.asarray(lens))
    for i, ln in enumerate(lens):
        c1 = T.init_cache(cfg, T.CacheSpec(max_seq=40, batch=1),
                          dtype=jnp.float32)
        lg1, c1 = T.prefill(params, cfg, jnp.asarray(toks[i: i + 1, :ln]),
                            c1, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lg[i]), np.asarray(lg1[0]),
                                   rtol=1e-4, atol=1e-4)
        _assert_slot_state_matches(cfg, _slot_view(cache_r, i), c1, int(ln))


@pytest.mark.parametrize("arch", KINDS)
def test_decode_inactive_slots_frozen(arch):
    """A masked decode step leaves inactive slots' cache (every kind of
    leaf) and clock bitwise untouched while active slots advance."""
    cfg, params, _ = _setup(arch, batch=3)
    lens = np.array([5, 12, 8], np.int32)
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab, (3, 12)).astype(np.int32)
    cache = T.init_cache(cfg, T.CacheSpec(max_seq=32, batch=3),
                         dtype=jnp.float32)
    _, cache = T.prefill(params, cfg, jnp.asarray(toks), cache,
                         compute_dtype=jnp.float32,
                         lengths=jnp.asarray(lens))
    active = jnp.asarray(np.array([True, False, True]))
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (3, 1)).astype(np.int32))
    logits, cache2 = T.decode_step(params, cfg, cache, tok,
                                   compute_dtype=jnp.float32, active=active)
    frozen_before = jax.tree.leaves(_slot_view(cache, 1))
    frozen_after = jax.tree.leaves(_slot_view(cache2, 1))
    for a, b in zip(frozen_before, frozen_after, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(cache2["t"]),
                                  np.asarray(cache["t"]) + [1, 0, 1])
    # and the active slots see exactly what an all-active step computes
    logits_all, _ = T.decode_step(params, cfg, cache, tok,
                                  compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(logits[0]),
                                  np.asarray(logits_all[0]))


@pytest.mark.parametrize("bucket", [1, 8])
@pytest.mark.parametrize("arch", KINDS)
def test_admit_group_leaves_inflight_slots_untouched(arch, bucket):
    """Three picks of different bucketed widths admitted in one tick,
    each by its own batch-1 prefill, next to two slots in flight: the
    in-flight slots' cache leaves and clocks are bitwise unchanged by
    the admission, and every request still emits exactly the tokens
    per-request `generate` produces."""
    cfg, params, scfg = _setup(arch, batch=5)
    rng = np.random.default_rng(8)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=g)
            for u, (n, g) in enumerate([(6, 8), (11, 7), (5, 5), (13, 6),
                                        (21, 4)])]
    sched = Scheduler(params, cfg, scfg, prefill_bucket=bucket)
    for r in reqs[:2]:
        sched.submit(r)
    sched.step()
    prefills, decoded = [], []
    prefill, decode = sched._prefill, sched._decode
    sched._prefill = lambda *a: prefills.append(a[1].shape) or prefill(*a)
    sched._decode = lambda p, c, *a: decoded.append(c) or decode(p, c, *a)
    before = sched.cache
    for r in reqs[2:]:
        sched.submit(r)
    sched.step()
    assert len({w for _, w in prefills}) == 3
    assert all(b == 1 for b, _ in prefills)
    for i in (0, 1):
        for a, b in zip(jax.tree.leaves(_slot_view(before, i)),
                        jax.tree.leaves(_slot_view(decoded[0], i)),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    comps = sched.run(max_steps=300)
    scfg1 = dataclasses.replace(scfg, batch=1)
    for r in reqs:
        ref = serve_lib.generate(params, cfg, scfg1,
                                 jnp.asarray(r.prompt)[None],
                                 r.max_new_tokens)
        np.testing.assert_array_equal(comps[r.uid].tokens, np.asarray(ref)[0],
                                      err_msg=f"{arch} uid={r.uid}")


def test_one_prefill_program_per_width():
    """Admitting groups of 1 to 8 picks at every bucketed width traces
    exactly one prefill program per width, whatever the slots and the
    number of picks: the set of programs one single-pick admit per
    width warms (as the benchmark's set-up does) is all a run needs."""
    from repro.serve_lib import scheduler as scheduler_mod

    cfg, params, scfg = _setup("qwen2-1.5b", batch=8, max_seq=32)
    scheduler_mod._jitted_steps.cache_clear()
    sched = Scheduler(params, cfg, scfg, prefill_bucket=8)
    widths = (8, 16, 24, 32)
    rng = np.random.default_rng(9)
    uid = 0
    for n in range(1, 9):
        for w in widths:
            for _ in range(n):
                plen = int(rng.integers(w - 7, min(w, scfg.max_seq - 1) + 1))
                sched.submit(Request(uid=uid, prompt=rng.integers(
                    0, cfg.vocab, plen).astype(np.int32), max_new_tokens=1))
                uid += 1
            sched.step()
            assert not sched.queue and not sched.n_active
    assert sched.stats["prefill_calls"] == uid
    assert sched.stats["prefill_widths"] == set(widths)
    assert sched._prefill._cache_size() == len(widths)


# --------------------------------------------------------------------------
# Engine: the decode step's fixed shapes are fully covered by plan_arch
# --------------------------------------------------------------------------


@pytest.mark.parametrize("arch", KINDS)
def test_decode_plan_coverage(arch):
    """plan_arch(decode_batch=B) pre-decides every engine request a
    decode-step trace makes: tracing inside a warm-started engine adds
    hits but ZERO new plan misses (no per-step re-planning)."""
    cfg = _cfg(arch)
    B = 3
    plan = engine_mod.plan_arch(cfg, seq_len=16, dtype_bytes=4,
                                decode_batch=B, backend="xla-einsum")
    eng = engine_mod.Engine(backend="xla-einsum", plan=plan)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, T.CacheSpec(max_seq=32, batch=B),
                         dtype=jnp.float32)
    cache = {**cache, "t": jnp.array([5, 9, 2], jnp.int32)}
    misses_before = plan.misses
    with engine_mod.use_engine(eng):
        step = jax.jit(lambda p, c, tok: T.decode_step(
            p, cfg, c, tok, compute_dtype=jnp.float32,
            active=jnp.array([True, True, False])))
        logits, _ = step(params, cache, jnp.zeros((B, 1), jnp.int32))
        logits.block_until_ready()
    assert plan.misses == misses_before
    if any(k in ("attn", "local", "rglru") for k in cfg.layer_pattern):
        assert plan.hits > 0  # ssm-only archs route no decode matmuls


# --------------------------------------------------------------------------
# Chunked prefill (DESIGN.md §12): chunked == unchunked, all postures
# --------------------------------------------------------------------------


def _mix_requests(cfg, rng, n_short=3, long_len=24):
    """One ingestion-forcing long prompt plus short interactive ones,
    with prompts fixed once so both serves see identical requests."""
    reqs = [Request(uid=0,
                    prompt=rng.integers(0, cfg.vocab, long_len)
                    .astype(np.int32),
                    max_new_tokens=6)]
    for uid in range(1, n_short + 1):
        plen = int(rng.integers(3, 8))
        reqs.append(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab, plen)
            .astype(np.int32), max_new_tokens=6))
    return reqs


def _clone(reqs):
    return [dataclasses.replace(r) for r in reqs]


@pytest.mark.parametrize("arch", KINDS)
def test_chunked_prefill_matches_unchunked(arch):
    """A long prompt streamed in CHUNK-token slices emits exactly the
    tokens monolithic admission produces, on every cache kind."""
    cfg, params, scfg = _setup(arch, batch=2)
    reqs = _mix_requests(cfg, np.random.default_rng(3))
    plain = Scheduler(params, cfg, scfg).run(_clone(reqs), max_steps=300)
    chunked_scfg = dataclasses.replace(scfg, prefill_chunk=CHUNK)
    sched = Scheduler(params, cfg, chunked_scfg)
    chunked = sched.run(_clone(reqs), max_steps=300)
    assert sorted(chunked) == sorted(plain)
    for uid in plain:
        np.testing.assert_array_equal(chunked[uid].tokens, plain[uid].tokens,
                                      err_msg=f"{arch} uid={uid}")
    # the long prompt actually went through the ingestion plane
    assert CHUNK in sched.stats["prefill_widths"]


@pytest.mark.parametrize("posture", ["paged", "int8", "paged-int8"])
def test_chunked_prefill_paged_and_int8(posture):
    """Chunk boundaries stay exact on the paged and int8 cache layouts
    (chunk % page_size == 0 keeps hist page-aligned; the int8 contract
    is greedy-token parity, as everywhere in the int8 plane)."""
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    over = {}
    if "paged" in posture:
        over.update(cache_layout="paged", page_size=8)
    if "int8" in posture:
        over.update(cache_dtype=jnp.int8)
    scfg = dataclasses.replace(scfg, **over)
    reqs = _mix_requests(cfg, np.random.default_rng(4))
    plain = Scheduler(params, cfg, scfg).run(_clone(reqs), max_steps=300)
    chunked_scfg = dataclasses.replace(scfg, prefill_chunk=CHUNK)
    chunked = Scheduler(params, cfg, chunked_scfg).run(_clone(reqs),
                                                       max_steps=300)
    for uid in plain:
        np.testing.assert_array_equal(chunked[uid].tokens, plain[uid].tokens,
                                      err_msg=f"{posture} uid={uid}")


def test_chunked_composes_with_speculative():
    """speculate_k drafts only after a slot finishes ingesting, so
    chunking + speculation stays bitwise identical to plain greedy."""
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2, max_seq=50)
    reqs = _mix_requests(cfg, np.random.default_rng(5))
    plain = Scheduler(params, cfg, scfg).run(_clone(reqs), max_steps=300)
    spec_scfg = dataclasses.replace(scfg, prefill_chunk=CHUNK,
                                    speculate_k=2, draft="self")
    chunked = Scheduler(params, cfg, spec_scfg).run(_clone(reqs),
                                                    max_steps=300)
    for uid in plain:
        np.testing.assert_array_equal(chunked[uid].tokens, plain[uid].tokens,
                                      err_msg=f"uid={uid}")


def test_chunk_width_validation():
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    bad = dataclasses.replace(scfg, prefill_chunk=6)
    with pytest.raises(ValueError, match="prefill_bucket"):
        Scheduler(params, cfg, bad, prefill_bucket=4)
    with pytest.raises(ValueError, match="page_size"):
        dataclasses.replace(scfg, prefill_chunk=12,
                            cache_layout="paged", page_size=8)


# --------------------------------------------------------------------------
# Async ingestion plane (DESIGN.md §12): parity, backpressure, shutdown
# --------------------------------------------------------------------------


def test_serve_async_matches_run():
    """Futures resolve to exactly the Completions the synchronous loop
    produces, including through the chunked ingestion path."""
    cfg, params, scfg = _setup("qwen2-1.5b", batch=2)
    scfg = dataclasses.replace(scfg, prefill_chunk=CHUNK)
    reqs = _mix_requests(cfg, np.random.default_rng(6))
    ref = Scheduler(params, cfg, scfg).run(_clone(reqs), max_steps=300)
    sched = Scheduler(params, cfg, scfg)
    with sched.serve_async(max_queue=len(reqs)) as srv:
        futs = {r.uid: srv.submit(r) for r in _clone(reqs)}
        comps = {uid: f.result(timeout=120) for uid, f in futs.items()}
    for uid in ref:
        np.testing.assert_array_equal(comps[uid].tokens, ref[uid].tokens)
        assert comps[uid].finish_reason == ref[uid].finish_reason
    assert not sched.n_active and not sched.queue


def test_async_backpressure_and_clean_shutdown():
    """A full bounded queue raises queue.Full under a submit timeout;
    shutdown drains accepted work and then refuses new submissions."""
    import queue as queue_mod

    cfg, params, scfg = _setup("qwen2-1.5b", batch=1)
    reqs = _mix_requests(cfg, np.random.default_rng(7), n_short=1)
    sched = Scheduler(params, cfg, scfg)
    srv = sched.serve_async(max_queue=1, start=False)  # worker not running
    fut0 = srv.submit(reqs[0])            # fills the queue
    with pytest.raises(queue_mod.Full):
        srv.submit(reqs[1], timeout=0.05)  # backpressure surfaces
    srv.start()
    srv.shutdown(wait=True)               # drains the accepted request
    assert fut0.result(timeout=5).finish_reason == "length"
    with pytest.raises(RuntimeError, match="shutdown"):
        srv.submit(reqs[1])
    # a rejected request surfaces on ITS future, not in the worker
    sched2 = Scheduler(params, cfg, scfg)
    with sched2.serve_async() as srv2:
        good = srv2.submit(reqs[0])
        bad = srv2.submit(Request(uid=reqs[0].uid,  # duplicate uid
                                  prompt=reqs[1].prompt, max_new_tokens=2))
        assert good.result(timeout=120).finish_reason == "length"
        with pytest.raises(ValueError):
            bad.result(timeout=120)
