"""Pallas TPU flash attention — the §Perf fix for the memory-dominated
attention cells.

The dry-run rooflines show the XLA-lowered chunked attention streaming
its (B, H, Sq, C) logits/probability tensors through HBM (e.g.
internvl2-1b prefill_32k: memory term 16.6 s, useful-FLOPs 0.05).  On
TPU these intermediates belong in VMEM: this kernel keeps the online-
softmax state (m, l, acc) in VMEM scratch across the KV-block sweep, so
per-layer HBM traffic drops to q + k + v + o.

Layout: grid (B*H, nq, nk) with the KV axis innermost — the scratch
state for one (batch*head, q-block) survives consecutive nk steps
(same revisiting guarantee the OS GEMM kernel uses).  Causality and
sliding windows are applied via broadcasted iota against the absolute
block offsets, fused in-kernel (no materialized mask).

Validated in interpret mode against models/layers.flash_attention's
naive oracle across shapes x causal x window (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            n_k: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        bq: int = 512, bk: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q (B, H, Sq, D); k, v (B, H, Sk, D) (repeat GQA heads outside).
    Sq % bq == 0 and Sk % bk == 0 (callers pad); D should be a multiple
    of 128 on real hardware."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sq % bq or sk % bk:
        raise ValueError(f"seq dims ({sq},{sk}) not divisible by ({bq},{bk})")
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    n_q, n_k = sq // bq, sk // bk
    grid = (bh, n_q, n_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), causal=causal,
                          window=window, bq=bq, bk=bk, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, iq, ik: (bh_, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, iq, ik: (bh_, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh_, iq, ik: (bh_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)


def attention_hbm_bytes(b: int, h: int, sq: int, sk: int, d: int,
                        itemsize: int = 2) -> int:
    """Kernelized per-layer HBM traffic: q + k + v + o only — the number
    the §Perf iteration uses to re-model the memory term."""
    return itemsize * b * h * d * (2 * sq + 2 * sk)


def _legal_block(seq: int, want: int) -> int:
    """Largest divisor of `seq` that is <= want (the kernel requires
    Sq % bq == 0 / Sk % bk == 0; engine decisions are hints).  When no
    usable divisor exists near the hint (prime-ish lengths would degrade
    to 1-row blocks), span the sequence with one block — but only while
    that block stays VMEM-sized; beyond that, fail with intent rather
    than hand Mosaic a whole-sequence tile."""
    b = min(want, seq)
    while seq % b:
        b -= 1
    if b >= 8 or b == seq:
        return b
    if seq <= 2048:  # one block spans the seq; the score tile stays VMEM-sized
        return seq
    raise ValueError(
        f"no usable attention block for seq={seq} (largest divisor <= "
        f"{want} is {b}); pad the sequence to a multiple of 8")


def register_into(registry) -> None:
    """Register flash attention as the `attention` op of both Pallas
    backends (repro.engine.KernelRegistry)."""
    def _run(interpret: bool):
        def run(decision, q, k, v, *, causal=True, window=0):
            bq = _legal_block(q.shape[2], decision.bm)
            bk = _legal_block(k.shape[2], decision.bn)
            return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                       bq=bq, bk=bk, interpret=interpret)
        return run

    registry.register("pallas-tpu", "attention", _run(interpret=False))
    registry.register("pallas-interpret", "attention", _run(interpret=True))
