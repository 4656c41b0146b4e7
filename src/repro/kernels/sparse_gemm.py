"""N:M structured-sparse GEMM as a Pallas TPU kernel + sparse backends.

The sparsity plane's execution layer (DESIGN.md §10): the right operand
arrives compressed (`sparse.SparseTensor` storage — kept values + int8
in-group offsets), the kernel scatters each compressed block back to a
dense (bk, bn) VMEM tile with an M-way one-hot accumulation (static
unroll over the group size — no gather instruction needed), and the
MXU runs a dense f32 dot on the reconstructed tile:

    w[g*M + off, n] = sum_j values[g*N + j, n] * [indices[g*N + j, n] == off]
    y = a @ w                                  (f32 accumulate, OS dataflow)

What sparsity buys on this path is BYTES, not MACs: the weight HBM
stream shrinks to density x value-bytes + one index byte per kept value
(1.6x for 2:4 float, 3.5x for sparse×int8), while the reconstruction
lives entirely in VMEM.  The effective-FLOPs story — a sparsity-aware
array skipping pruned groups, FlexSA-style — is the COST MODELS' view
(`TPUModel`/`AnalyticalCostModel` plan `gemm_sparse` at K_eff =
density x K); this kernel is the TPU-honest executor of that decision.

Two backends register into the engine registry:

  pallas-tpu-sparse  this module's OS-dataflow scatter kernel (f32 VMEM
                     scratch accumulator; interpret mode auto-resolves
                     off-TPU like the other Pallas backends);
  xla-sparse         the reference: the same scatter in plain jnp + one
                     `jnp.dot` — numerics oracle and the CPU-CI path.

The two are BIT-EXACT whenever the K reduction fits one block (the
default block chooser covers padded K up to its VMEM-gated cap, so
every test/bench shape takes the single-block path): both sides build
the dense tile with the identical `_scatter_dense` sum and reduce K in
one f32 dot.  Multi-block K accumulates per block and may differ in the
last ulp, like any split reduction.

VJP policy (QAT posture, mirroring the int8 plane): cotangents flow
DENSE to the activations (dA = g @ densify(W)^T in float); the weight
cotangent is gathered back through the index metadata, so pruned
positions get exactly zero gradient — training nudges only the kept
values and the mask stays frozen.  Sparse×int8 storage (int8 values +
per-column scales) is data, not a trainable leaf: its weight cotangent
is None, like `gemm_w8`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.engine.backends import _diff_gemm, auto_interpret

from .redas_gemm import SUBLANE, VMEM_BYTES, round_up

LANE = 128


def sparse_vmem_bytes(bm: int, bk: int, bn: int, n_keep: int,
                      m_group: int) -> int:
    """Working set of one grid step, sized at f32 operands (x2 for the
    pipeline's double buffering): the activation block, the compressed
    value + index blocks, the reconstructed dense weight tile, and the
    f32 accumulator."""
    bk_c = bk * n_keep // m_group
    return (2 * (bm * bk * 4 + bk_c * bn * 4 + bk_c * bn)
            + bk * bn * 4 + bm * bn * 4)


def _bk_unit(m_group: int) -> int:
    """K blocks must tile both the VREG lane (128) and the N:M group."""
    return math.lcm(LANE, m_group)


def default_sparse_blocks(m: int, k_dense: int, n: int, n_keep: int,
                          m_group: int) -> tuple[int, int, int]:
    """Hardware-aligned blocks, with bk covering the whole padded K
    reduction when the VMEM gate allows (single-block K keeps the
    Pallas kernel bit-exact against the XLA reference — module
    docstring); halve bk toward the unit otherwise."""
    unit = _bk_unit(m_group)
    bm = min(round_up(m, SUBLANE), 256)
    bk = min(round_up(k_dense, unit), 8 * unit)
    bn = min(round_up(n, LANE), 256)
    while (sparse_vmem_bytes(bm, bk, bn, n_keep, m_group) > VMEM_BYTES
           and bk > unit):  # pragma: no cover - huge-K guard
        bk = max(unit, round_up(bk // 2, unit))
    return bm, bk, bn


def _scatter_dense(values, indices, n_keep: int, m_group: int):
    """Expand compressed (K_c, N) storage to the dense (K_c//N*M, N)
    tile: a one-hot sum over the in-group offset, unrolled statically
    over the group size.  Shared verbatim by the Pallas kernel body and
    the XLA reference so the two construct bit-identical tiles.  The
    offset compare runs in int32: the TPU vector unit has no int8
    comparison."""
    k_c, bn = values.shape
    groups = k_c // n_keep
    v3 = values.reshape(groups, n_keep, bn)
    i3 = indices.reshape(groups, n_keep, bn).astype(jnp.int32)
    planes = [jnp.sum(jnp.where(i3 == off, v3, 0.0), axis=1)
              for off in range(m_group)]
    return jnp.stack(planes, axis=1).reshape(groups * m_group, bn)


# ---------------------------------------------------------------------------
# The Pallas kernel: OS dataflow, f32 VMEM scratch accumulator
# ---------------------------------------------------------------------------


def _sparse_os_kernel(a_ref, v_ref, i_ref, o_ref, acc_ref, *, n_k: int,
                      n_keep: int, m_group: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    w = _scatter_dense(v_ref[...].astype(jnp.float32), i_ref[...],
                       n_keep, m_group)
    acc_ref[...] += jnp.dot(a, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_keep", "m_group", "bm", "bk", "bn", "interpret"))
def gemm_sparse(a: jax.Array, values: jax.Array, indices: jax.Array, *,
                n_keep: int, m_group: int, bm: int, bk: int, bn: int,
                interpret: bool = False) -> jax.Array:
    """Blocked (M, K) @ N:M-compressed (K_c, N) -> f32 (M, N); dims
    must be multiples of the blocks (`sparse_gemm` pads arbitrary
    shapes).  OS only: the f32 accumulator and the reconstructed dense
    weight tile both live in VMEM — streaming the scatter through HBM
    would forfeit exactly the byte shrink sparsity buys."""
    m, k = a.shape
    k_c, n = values.shape
    if k_c * m_group != k * n_keep:
        raise ValueError(
            f"compressed K {k_c} does not match dense K {k} at "
            f"{n_keep}:{m_group}")
    if values.shape != indices.shape:
        raise ValueError(
            f"values {values.shape} / indices {indices.shape} mismatch")
    if m % bm or k % bk or n % bn:
        raise ValueError(
            f"({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})")
    if bm % SUBLANE or bk % _bk_unit(m_group) or bn % LANE:
        raise ValueError(
            f"sparse blocks ({bm},{bk},{bn}) must be multiples of "
            f"({SUBLANE}, {_bk_unit(m_group)}, {LANE})")
    gm, gk, gn = m // bm, k // bk, n // bn
    bk_c = bk * n_keep // m_group
    return pl.pallas_call(
        functools.partial(_sparse_os_kernel, n_k=gk, n_keep=n_keep,
                          m_group=m_group),
        grid=(gm, gn, gk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk_c, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((bk_c, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(a, values, indices)


# ---------------------------------------------------------------------------
# Shape-safe entry point (pad -> kernel -> rescale -> slice)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n_keep", "m_group", "interpret", "use_pallas",
                     "out_dtype"))
def sparse_gemm(a: jax.Array, values: jax.Array, indices: jax.Array,
                scale: jax.Array | None = None, *, n_keep: int = 2,
                m_group: int = 4, interpret: bool = False,
                use_pallas: bool = True, out_dtype=None) -> jax.Array:
    """Float (M, K) @ N:M-compressed storage for arbitrary dims.

    `values`/`indices` are `sparse.SparseTensor` children (K_c, N) with
    K_c = ceil(K / M) * N; `scale` (1, N) or (N,) float32 marks
    sparse×int8 storage and rescales the f32 accumulator once per
    output column (exact: per-column scales factor out of the
    K-contraction).  Zero-padding is exact — padded compressed rows
    scatter zero tiles."""
    out_dtype = out_dtype or a.dtype
    m, k = a.shape
    k_c, n = values.shape
    groups = k_c // n_keep
    k_store = groups * m_group  # dense K padded to the group size
    if use_pallas:
        bm, bk, bn = default_sparse_blocks(m, k_store, n, n_keep, m_group)
        mp, kp, np_ = round_up(m, bm), round_up(k_store, bk), round_up(n, bn)
        kp_c = kp * n_keep // m_group
        a_p = (jnp.pad(a, ((0, mp - m), (0, kp - k)))
               if (mp, kp) != (m, k) else a)
        if (kp_c, np_) != (k_c, n):
            v_p = jnp.pad(values, ((0, kp_c - k_c), (0, np_ - n)))
            i_p = jnp.pad(indices, ((0, kp_c - k_c), (0, np_ - n)))
        else:
            v_p, i_p = values, indices
        acc = gemm_sparse(a_p, v_p, i_p, n_keep=n_keep, m_group=m_group,
                          bm=bm, bk=bk, bn=bn, interpret=interpret)
        acc = acc[:m, :n] if (mp, np_) != (m, n) else acc
    else:
        w = _scatter_dense(values.astype(jnp.float32), indices,
                           n_keep, m_group)
        a_f = a.astype(jnp.float32)
        if k_store != k:
            a_f = jnp.pad(a_f, ((0, 0), (0, k_store - k)))
        acc = jnp.dot(a_f, w, preferred_element_type=jnp.float32)
    if scale is not None:
        acc = acc * scale.reshape(1, -1)
    return acc.astype(out_dtype)


# ---------------------------------------------------------------------------
# Dispatch-layer custom VJPs (masked weight cotangents — module docstring)
# ---------------------------------------------------------------------------


def _float_gemm(a, b, *, use_pallas, interpret, out_dtype):
    """The dense GEMM the backward pass runs on: Pallas (engine block
    defaults, VMEM-gated) on the Pallas backend, XLA otherwise."""
    if use_pallas:
        from repro.engine.backends import pallas_gemm  # lazy: avoids cycle

        return pallas_gemm(a, b, interpret=interpret, out_dtype=out_dtype)
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


@functools.lru_cache(maxsize=None)
def _diff_sparse_gemm(n_keep, m_group, interpret, use_pallas, out_dtype):
    """Differentiable sparse GEMM over FLOAT compressed values:
    activations get the dense cotangent (dA = g @ densify(W)^T), the
    values get the dense weight cotangent GATHERED at the kept
    positions (pruned positions receive exactly zero — densifying dV
    reproduces a masked dense gradient), and the frozen index metadata
    gets None."""

    @jax.custom_vjp
    def f(a, values, indices):
        return sparse_gemm(a, values, indices, n_keep=n_keep,
                           m_group=m_group, interpret=interpret,
                           use_pallas=use_pallas, out_dtype=out_dtype)

    def fwd(a, values, indices):
        return f(a, values, indices), (a, values, indices)

    def bwd(res, g):
        a, values, indices = res
        m, k = a.shape
        k_c, n = values.shape
        groups = k_c // n_keep
        k_store = groups * m_group
        g = g.astype(a.dtype)
        w = _scatter_dense(values.astype(jnp.float32), indices,
                           n_keep, m_group).astype(a.dtype)
        da = _float_gemm(g, w[:k].T, use_pallas=use_pallas,
                         interpret=interpret, out_dtype=a.dtype)
        dw = _float_gemm(a.T, g, use_pallas=use_pallas, interpret=interpret,
                         out_dtype=jnp.float32)
        if k_store != k:
            dw = jnp.pad(dw, ((0, k_store - k), (0, 0)))
        dw3 = dw.reshape(groups, m_group, n)
        i3 = indices.reshape(groups, n_keep, n).astype(jnp.int32)
        dv = jnp.take_along_axis(dw3, i3, axis=1)
        return da, dv.reshape(k_c, n).astype(values.dtype), None

    f.defvjp(fwd, bwd)
    # jit the wrapper: an un-jitted custom_vjp call re-traces eagerly
    # (~200 us/call — the BENCH_PR3 lesson).
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _diff_sparse_gemm_q(n_keep, m_group, interpret, use_pallas, out_dtype):
    """Differentiable sparse×int8 GEMM: gradients flow to the
    ACTIVATIONS only (int8 storage is data, not a trainable leaf —
    same posture as `gemm_w8`)."""

    @jax.custom_vjp
    def f(a, values, indices, scale):
        return sparse_gemm(a, values, indices, scale, n_keep=n_keep,
                           m_group=m_group, interpret=interpret,
                           use_pallas=use_pallas, out_dtype=out_dtype)

    def fwd(a, values, indices, scale):
        return f(a, values, indices, scale), (a, values, indices, scale)

    def bwd(res, g):
        a, values, indices, scale = res
        k = a.shape[1]
        g = g.astype(a.dtype)
        w = (_scatter_dense(values.astype(jnp.float32), indices,
                            n_keep, m_group)
             * scale.reshape(1, -1)).astype(a.dtype)
        da = _float_gemm(g, w[:k].T, use_pallas=use_pallas,
                         interpret=interpret, out_dtype=a.dtype)
        return da, None, None, None

    f.defvjp(fwd, bwd)
    return jax.jit(f)


# ---------------------------------------------------------------------------
# Engine registration
# ---------------------------------------------------------------------------


def _sparse_backend(use_pallas: bool):
    def run(decision, a, values, indices, scale=None, *, n_keep=2,
            m_group=4, out_dtype=None):
        if scale is not None:
            fn = _diff_sparse_gemm_q(n_keep, m_group, auto_interpret(None),
                                     use_pallas, out_dtype)
            return fn(a, values, indices, scale)
        fn = _diff_sparse_gemm(n_keep, m_group, auto_interpret(None),
                               use_pallas, out_dtype)
        return fn(a, values, indices)
    return run


def _dense_gemm_backend(use_pallas: bool):
    """Float `gemm` on the sparse backends — a sparse server's
    non-pruned matmuls (skip-listed weights, lm head via module matmul)
    still dispatch somewhere."""
    def run(decision, a, b, *, out_dtype=None):
        if use_pallas:
            fn = _diff_gemm(decision.dataflow, decision.bm, decision.bk,
                            decision.bn, auto_interpret(None), out_dtype)
            return fn(a, b)
        return _float_gemm(a, b, use_pallas=False, interpret=False,
                           out_dtype=out_dtype or a.dtype)
    return run


def register_into(registry) -> None:
    """Register the structured-sparsity execution plane: the Pallas
    backend ("pallas-tpu-sparse", interpret auto-resolved off-TPU) and
    the XLA reference ("xla-sparse")."""
    from repro.engine.backends import (_xla_attention,  # lazy: avoids cycle
                                       _xla_grouped)

    for name, use_pallas in (("pallas-tpu-sparse", True),
                             ("xla-sparse", False)):
        registry.register(name, "gemm_sparse", _sparse_backend(use_pallas))
        registry.register(name, "gemm", _dense_gemm_backend(use_pallas))
        # MoE expert stacks are never pruned (prune_params skips them)
        # and attention stays float; registering the references keeps
        # the backend namespace total — same posture as the int8 plane.
        registry.register(name, "grouped_gemm", _xla_grouped)
        registry.register(name, "attention", _xla_attention)
