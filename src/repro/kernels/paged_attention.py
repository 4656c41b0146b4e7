"""Paged-attention decode: gather KV pages through a block table.

The serving plane's paged layout (DESIGN.md §8) stores attention KV in
a pool `(P, page, KV, hd)` shared by all slots; each slot's logical
rows live at the physical pages named by its block-table row
`(n_bt,) int32` (-1 = unallocated).  Decode attention then needs a
gather the contiguous flash kernel cannot express — so this module
provides the `paged_attention` op in both guises:

  paged_attention_reference   pure-jax gather + EXACTLY the contiguous
                              `models.layers.cached_attention` math
                              (same einsums, same masking) so paged vs
                              contiguous greedy decode is bit-identical
                              — the parity oracle the tests lean on.
  paged_attention_tpu         Pallas kernel, grid (B, n_bt): the
                              block table and per-slot kv_len ride the
                              scalar-prefetch lane and each grid step's
                              k/v BlockSpec index map dereferences
                              bt[b, i] directly — a slot's pages stream
                              HBM->VMEM exactly once, no gathered copy
                              of the cache ever materializes.
                              Online-softmax scratch carries (m, l,
                              acc) across the page sweep, flash-style.

int8 composition (PR 5 codec): per-row scales page with their rows —
`k_scale_pages`/`v_scale_pages` pools `(P, page, KV)` are indexed by
the SAME block table, and the kernel folds scales in where the
contiguous path does (scores *= k_scale before masking, weights *=
v_scale after normalizing by the plain softmax denominator).

Unallocated table entries clamp to page 0; every position of such a
page is >= kv_len, so its scores mask to NEG_INF and contribute an
exact 0 — stale or foreign rows never leak into the output.  A fully
masked slot (kv_len == 0, i.e. inactive) outputs exact zeros in the
kernel via the running-max == NEG_INF guard; the pure-jax reference
softmaxes all-NEG_INF rows to a uniform average instead, so the two
paths agree only for kv_len >= 1 (all live slots).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              kv_len: jax.Array, *,
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None) -> jax.Array:
    """q (B, 1, H, d); k/v pools (P, page, KV, hd); block_tables
    (B, n_bt) int32 (-1 = hole); kv_len (B,).  Returns o (B, 1, H, d)
    pre-`wo` (the caller owns the output projection).

    The gather reproduces each slot's logical rows [0, n_bt*page) in
    order, after which the math is line-for-line cached_attention: rows
    at positions >= kv_len score NEG_INF, exp underflows to exact 0.0,
    and x + 0.0 == x — so the result is bitwise what the contiguous
    cache produces for the same live rows."""
    b, sq, h, d = q.shape
    kv = k_pages.shape[2]
    g = h // kv
    n_pool = k_pages.shape[0]
    safe = jnp.clip(block_tables, 0, n_pool - 1)            # (B, n_bt)
    n_bt, page = block_tables.shape[1], k_pages.shape[1]
    s_rows = n_bt * page
    k = k_pages[safe].reshape(b, s_rows, kv, d)
    v = v_pages[safe].reshape(b, s_rows, kv, d)
    row = lambda sc: sc.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    qg = (q.reshape(b, sq, kv, g, d) / math.sqrt(d)).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    if k_scale is not None:
        s = s * row(k_scale[safe].reshape(b, s_rows, kv))
    srange = jnp.arange(s_rows)
    if kv_len.ndim == 1:
        valid = (srange[None, :] < kv_len[:, None])[:, None, :]   # (B,1,S)
    else:  # per-query lengths (B, Sq) — the W-wide speculative verify
        valid = srange[None, None, :] < kv_len[:, :, None]        # (B,Sq,S)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p_attn = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p_attn = p_attn * row(v_scale[safe].reshape(b, s_rows, kv))
    o = jnp.einsum("bkgqs,bskd->bqkgd", p_attn, v.astype(jnp.float32))
    return o.reshape(b, sq, h, d).astype(q.dtype)


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, scale: float,
            page: int, kv: int, g: int, n_bt: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One page of every KV head arrives as (page * KV, d) rows; row
    # c holds position c // KV of KV head c % KV, and query head r reads
    # KV head r // G.  The kernel scores every (query head, row) pair
    # and masks the pairs of different heads, so no sublane slice of the
    # page is ever taken.
    q = q_ref[0].astype(jnp.float32) * scale                # (H, d)
    k = k_ref[0].astype(jnp.float32)                        # (page*KV, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if quantized:
        s = s * ks_ref[0].astype(jnp.float32)               # (1, page*KV)

    shape = (q.shape[0], page * kv)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    pos = i * page + col // kv
    s = jnp.where((col % kv == row // g) & (pos < len_ref[b]), s, NEG_INF)

    m_prev = m_ref[...]                                     # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # When every position so far is masked (kv_len == 0), m_new is still
    # NEG_INF and exp(s - m_new) would be exp(0) = 1 — guard so fully
    # masked rows contribute an exact 0 instead of averaging page-0 v.
    dead = m_new == NEG_INF
    p = jnp.where(dead, 0.0, jnp.exp(s - m_new))
    corr = jnp.where(dead, 0.0, jnp.exp(m_prev - m_new))
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    if quantized:
        p = p * vs_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == n_bt - 1)
    def _flush():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_tpu(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, kv_len: jax.Array,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None, *,
                        interpret: bool = False) -> jax.Array:
    """Same contract as `paged_attention_reference` (sq must be 1).

    The block table and kv_len are scalar-prefetched: k/v (and scale)
    index maps read `bt[b, i]` to land each grid step's BlockSpec on
    the right physical page, so the sweep over a slot's pages is the
    only traffic.  Holes (-1) clamp to page 0 and mask to exact zero
    via the kv_len comparison."""
    b, sq, h, d = q.shape
    if sq != 1:
        raise ValueError(f"paged decode kernel is sq==1 only, got {sq}")
    n_pool, page, kv, _ = k_pages.shape
    g = h // kv
    n_bt = block_tables.shape[1]
    quantized = k_scale is not None

    # head r = kv_idx * g + g_idx (layers.py order) reads KV head r // g.
    # k/v blocks cover one page of the whole KV axis, flattened to
    # (page * KV, d) rows (a free row-major reshape of the pool), and
    # the scale blocks to (1, page * KV): the block's last two dims then
    # equal the array's, which the TPU tiling rule accepts for any KV.
    # One grid step serves every head of a slot, so each page streams
    # HBM->VMEM once; the kernel pairs heads with their rows by masking.
    def page_idx(b_, i_, bt, ln):
        return (jnp.maximum(bt[b_, i_], 0), 0, 0)

    def slot_idx(b_, i_, bt, ln):
        return (b_, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, d), slot_idx),
        pl.BlockSpec((1, page * kv, d), page_idx),
        pl.BlockSpec((1, page * kv, d), page_idx),
    ]
    args = [q.reshape(b, h, d), k_pages.reshape(n_pool, page * kv, d),
            v_pages.reshape(n_pool, page * kv, d)]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page * kv), page_idx),
                     pl.BlockSpec((1, 1, page * kv), page_idx)]
        args += [k_scale.reshape(n_pool, 1, page * kv),
                 v_scale.reshape(n_pool, 1, page * kv)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_bt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), slot_idx),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),    # running max
            pltpu.VMEM((h, 1), jnp.float32),    # running denominator
            pltpu.VMEM((h, d), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), page=page,
                          kv=kv, g=g, n_bt=n_bt, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_len.astype(jnp.int32), *args)
    return out.reshape(b, sq, h, d)


def register_into(registry) -> None:
    """Register the `paged_attention` op across the backend namespace:
    the reference gather on the XLA backends (exact-parity path) and
    the scalar-prefetch kernel on the Pallas ones."""
    def _reference(decision, q, k_pages, v_pages, block_tables, kv_len, *,
                   k_scale=None, v_scale=None):
        return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                         kv_len, k_scale=k_scale,
                                         v_scale=v_scale)

    def _pallas(interpret: bool | None):
        def run(decision, q, k_pages, v_pages, block_tables, kv_len, *,
                k_scale=None, v_scale=None):
            from repro.engine.backends import auto_interpret
            return paged_attention_tpu(q, k_pages, v_pages, block_tables,
                                       kv_len, k_scale, v_scale,
                                       interpret=auto_interpret(interpret))
        return run

    registry.register("xla-einsum", "paged_attention", _reference)
    registry.register("xla-int8", "paged_attention", _reference)
    registry.register("xla-sparse", "paged_attention", _reference)
    registry.register("pallas-tpu", "paged_attention", _pallas(False))
    registry.register("pallas-interpret", "paged_attention", _pallas(True))
    registry.register("pallas-tpu-int8", "paged_attention", _pallas(None))
    registry.register("pallas-tpu-sparse", "paged_attention", _pallas(None))
