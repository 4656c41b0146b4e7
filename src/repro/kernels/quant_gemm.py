"""Int8 GEMM as a Pallas TPU kernel + the engine's int8 backends.

The quantization plane's execution layer (DESIGN.md §7): both operands
arrive (or are dynamically made) int8, the MXU accumulates
int8 x int8 -> int32 (`preferred_element_type=jnp.int32` — on v5e the
int8 MXU path doubles peak throughput over bf16), and the int32
accumulator is rescaled ONCE per output element by the product of the
operands' per-channel scales:

    y[m, n] = (sum_k a_q[m, k] * b_q[k, n]) * s_a[m] * s_b[n]

which is exact because symmetric per-channel scales factor out of the
K-contraction (scales reduce the contraction axis — quant/quantize.py).

Two backends register into the engine registry:

  pallas-tpu-int8  this module's OS-dataflow Pallas kernel (int32 VMEM
                   scratch accumulator, one HBM write per output tile;
                   interpret mode auto-resolves off-TPU like the
                   pre-engine `auto_matmul` did, so one backend name
                   serves both hosts);
  xla-int8         the reference: the same quantization decomposition
                   through `lax.dot_general(..., preferred_element_type
                   =jnp.int32)` — numerics oracle and the CPU-CI path.

Both expose three ops: `gemm` (dynamic quantization of both operands),
`gemm_w8` (pre-quantized weights from `quant.quantize_params` + dynamic
per-row activation quantization), `grouped_gemm` (per-expert int8).

VJP policy: the forward is quantized, the backward is NOT — cotangents
are computed by plain float GEMMs in the residuals' compute dtype (bf16
in production), i.e. a straight-through estimator.  Quantization noise
is sub-resolution for gradients and an int8 backward would quantize the
*cotangent*, whose dynamic range per-channel scaling does not cover.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.engine.backends import auto_interpret
from repro.quant.quantize import kv_quantize, quantize

from .redas_gemm import VMEM_BYTES, round_up

# int8 VREG tiling floor: (sublane, lane) = (32, 128) — four times the
# f32 sublane because four int8 rows pack one 32-bit sublane word.
INT8_SUBLANE = 32
LANE = 128


def int8_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """Working set of one grid step: two int8 operand blocks (x2 for the
    pipeline's double buffering) + the int32 accumulator (Eq. 2 analogue
    at 1-byte operands — the footprint shrink that buys larger tiles)."""
    return 2 * (bm * bk + bk * bn) + bm * bn * 4


def align_int8_blocks(bm: int, bk: int, bn: int) -> tuple[int, int, int]:
    """Snap planner-chosen blocks to the int8 tiling floor and re-gate
    VMEM.  Cost-model decisions ladder from the f32 sublane (8); the
    int8 kernel's floor is (32, 128), so executed blocks round up —
    the decision stays the planning identity, execution aligns."""
    bm = round_up(bm, INT8_SUBLANE)
    bk = round_up(bk, LANE)
    bn = round_up(bn, LANE)
    while int8_vmem_bytes(bm, bk, bn) > VMEM_BYTES:  # pragma: no cover
        bk = max(LANE, bk // 2)
    return bm, bk, bn


def default_int8_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Hardware-aligned int8 blocks no larger than the padded problem."""
    return align_int8_blocks(min(round_up(m, INT8_SUBLANE), 256),
                             min(round_up(k, LANE), 512),
                             min(round_up(n, LANE), 256))


def quantize_rows(x):
    """Dynamic symmetric per-row activation quantization: x (M, K) float
    -> (q (M, K) int8, scale (M,) float32).  Per-row because the GEMM
    contracts K — the scale must not vary along the contraction.  ONE
    codec: this is the cache codec (`quant.kv_quantize`) applied to the
    last axis, so the property-tested round-trip bound covers both."""
    return kv_quantize(x)


def quantize_cols(x):
    """Per-column twin of `quantize_rows` for the right operand:
    x (K, N) float -> (q int8, scale (N,) float32) — the weight codec
    (`quant.quantize`, reduce axis 0) with the keepdim flattened."""
    qt = quantize(x, axis=0)
    return qt.q, qt.scale.reshape(-1)


# ---------------------------------------------------------------------------
# The Pallas kernel: OS dataflow, int32 VMEM scratch accumulator
# ---------------------------------------------------------------------------


def _int8_os_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def gemm_int8(a_q: jax.Array, b_q: jax.Array, *, bm: int, bk: int, bn: int,
              interpret: bool = False) -> jax.Array:
    """Blocked (M, K) @ (K, N), int8 x int8 -> int32; dims must be
    multiples of the blocks (`quant_gemm` pads arbitrary shapes).

    OS only: the int32 accumulator lives in VMEM scratch across the
    whole K-reduction — the streaming dataflows would push int32
    partial sums through HBM, forfeiting exactly the byte shrink that
    motivates int8 (an int32 partial stream is 4x the int8 operand
    traffic; see DESIGN.md §7)."""
    m, k = a_q.shape
    k2, n = b_q.shape
    if k != k2:
        raise ValueError(f"int8 GEMM dim mismatch {a_q.shape} @ {b_q.shape}")
    if m % bm or k % bk or n % bn:
        raise ValueError(
            f"({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})")
    if bm % INT8_SUBLANE or bk % LANE or bn % LANE:
        raise ValueError(
            f"int8 blocks ({bm},{bk},{bn}) must be multiples of "
            f"({INT8_SUBLANE}, {LANE}) (int8 VREG tiling floor)")
    gm, gk, gn = m // bm, k // bk, n // bn
    return pl.pallas_call(
        functools.partial(_int8_os_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(a_q, b_q)


# ---------------------------------------------------------------------------
# Shape-safe entry points (pad -> kernel -> rescale -> slice)
# ---------------------------------------------------------------------------


def _int32_matmul_q(a_q, b_q, *, bm, bk, bn, interpret, use_pallas):
    """Padded int8 matmul core shared by both backends; returns int32
    (M, N).  Zero padding is exact for integer accumulation."""
    m, k = a_q.shape
    n = b_q.shape[1]
    if not use_pallas:
        return jax.lax.dot_general(
            a_q, b_q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    a_p = jnp.pad(a_q, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a_q
    b_p = jnp.pad(b_q, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else b_q
    out = gemm_int8(a_p, b_p, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:m, :n] if (mp, np_) != (m, n) else out


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "use_pallas", "out_dtype"))
def quant_gemm(a: jax.Array, b: jax.Array, *, bm: int = 256, bk: int = 512,
               bn: int = 256, interpret: bool = False,
               use_pallas: bool = True, out_dtype=None) -> jax.Array:
    """Float (M, K) @ (K, N) through dynamic int8 quantization of BOTH
    operands: per-row scales on A, per-column on B, int32 accumulate,
    one rescale.  The drop-in int8 sibling of `engine.backends.pallas_gemm`."""
    out_dtype = out_dtype or a.dtype
    a_q, s_a = quantize_rows(a)
    b_q, s_b = quantize_cols(b)
    acc = _int32_matmul_q(a_q, b_q, bm=bm, bk=bk, bn=bn,
                          interpret=interpret, use_pallas=use_pallas)
    return (acc.astype(jnp.float32) * s_a[:, None] * s_b[None, :]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "use_pallas", "out_dtype"))
def quant_gemm_w8(a: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
                  bm: int = 256, bk: int = 512, bn: int = 256,
                  interpret: bool = False, use_pallas: bool = True,
                  out_dtype=None) -> jax.Array:
    """Float activations against PRE-quantized weights
    (`quant.quantize_params` storage: w_q (K, N) int8, w_scale (1, N) or
    (N,) float32) — the serving path that never materializes a float
    weight."""
    out_dtype = out_dtype or a.dtype
    a_q, s_a = quantize_rows(a)
    acc = _int32_matmul_q(a_q, w_q, bm=bm, bk=bk, bn=bn,
                          interpret=interpret, use_pallas=use_pallas)
    s_w = w_scale.reshape(-1)
    return (acc.astype(jnp.float32) * s_a[:, None] * s_w[None, :]).astype(out_dtype)


# ---------------------------------------------------------------------------
# Dispatch-layer custom VJPs (bf16 cotangents — see module docstring)
# ---------------------------------------------------------------------------


def _float_gemm(a, b, *, use_pallas, interpret, out_dtype):
    """The unquantized GEMM the backward pass runs on: Pallas (engine
    block defaults, VMEM-gated) on the Pallas backend, XLA otherwise."""
    if use_pallas:
        from repro.engine.backends import pallas_gemm  # lazy: avoids cycle

        return pallas_gemm(a, b, interpret=interpret, out_dtype=out_dtype)
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _diff_quant_gemm(bm, bk, bn, interpret, use_pallas, out_dtype):
    """Differentiable dynamic-quant GEMM: quantized forward, float
    backward (cotangents never quantize — dA = g @ B^T and dB = A^T @ g
    run in the residuals' compute dtype, bf16 in production)."""

    @jax.custom_vjp
    def f(a, b):
        return quant_gemm(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret,
                          use_pallas=use_pallas, out_dtype=out_dtype)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        g = g.astype(a.dtype)
        da = _float_gemm(g, b.T, use_pallas=use_pallas, interpret=interpret,
                         out_dtype=a.dtype)
        db = _float_gemm(a.T, g, use_pallas=use_pallas, interpret=interpret,
                         out_dtype=b.dtype)
        return da, db

    f.defvjp(fwd, bwd)
    # jit the wrapper: an un-jitted custom_vjp call re-traces eagerly
    # (~200 us/call — the BENCH_PR3 lesson).
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _diff_quant_gemm_w8(bm, bk, bn, interpret, use_pallas, out_dtype):
    """Differentiable w8 GEMM: gradients flow to the ACTIVATIONS only
    (dA = g @ dequant(W)^T in float); the stored int8 weight is data,
    not a trainable leaf."""

    @jax.custom_vjp
    def f(a, w_q, w_scale):
        return quant_gemm_w8(a, w_q, w_scale, bm=bm, bk=bk, bn=bn,
                             interpret=interpret, use_pallas=use_pallas,
                             out_dtype=out_dtype)

    def fwd(a, w_q, w_scale):
        return f(a, w_q, w_scale), (a, w_q, w_scale)

    def bwd(res, g):
        a, w_q, w_scale = res
        g = g.astype(a.dtype)
        w_f = (w_q.astype(jnp.float32)
               * w_scale.reshape(1, -1)).astype(a.dtype)
        da = _float_gemm(g, w_f.T, use_pallas=use_pallas,
                         interpret=interpret, out_dtype=a.dtype)
        return da, None, None

    f.defvjp(fwd, bwd)
    return jax.jit(f)


# ---------------------------------------------------------------------------
# Engine registration
# ---------------------------------------------------------------------------


def _blocks(decision) -> tuple[int, int, int]:
    return align_int8_blocks(decision.bm, decision.bk, decision.bn)


def _gemm_backend(use_pallas: bool):
    def run(decision, a, b, *, out_dtype=None):
        bm, bk, bn = _blocks(decision)
        fn = _diff_quant_gemm(bm, bk, bn, auto_interpret(None), use_pallas,
                              out_dtype)
        return fn(a, b)
    return run


def _gemm_w8_backend(use_pallas: bool):
    def run(decision, a, w_q, w_scale, *, out_dtype=None):
        bm, bk, bn = _blocks(decision)
        fn = _diff_quant_gemm_w8(bm, bk, bn, auto_interpret(None), use_pallas,
                                 out_dtype)
        return fn(a, w_q, w_scale)
    return run


def _grouped_backend(use_pallas: bool):
    def run(decision, x, w, *, out_dtype=None):
        """x (E, C, D) @ w (E, D, F) per expert, each through the int8
        path.  E is static, so the trace-time loop stays O(E) kernels —
        same posture as the float grouped kernel's per-expert grid."""
        bm, bk, bn = _blocks(decision)
        fn = _diff_quant_gemm(bm, bk, bn, auto_interpret(None), use_pallas,
                              out_dtype or x.dtype)
        outs = [fn(x[e], w[e]) for e in range(x.shape[0])]
        return jnp.stack(outs, axis=0)
    return run


def register_into(registry) -> None:
    """Register the int8 execution plane: the Pallas backend
    ("pallas-tpu-int8", interpret auto-resolved off-TPU) and the XLA
    reference ("xla-int8")."""
    from repro.engine.backends import _xla_attention  # lazy: avoids cycle

    for name, use_pallas in (("pallas-tpu-int8", True), ("xla-int8", False)):
        registry.register(name, "gemm", _gemm_backend(use_pallas))
        registry.register(name, "gemm_w8", _gemm_w8_backend(use_pallas))
        registry.register(name, "grouped_gemm", _grouped_backend(use_pallas))
        # attention stays float (the KV cache has its own int8 codec);
        # registering the reference keeps the backend namespace total.
        registry.register(name, "attention", _xla_attention)
