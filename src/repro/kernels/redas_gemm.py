"""ReDas GEMM as a Pallas TPU kernel: BlockSpec tiles play the logical
array, grid order + VMEM residency plays the dataflow.

Hardware adaptation (DESIGN.md Sec. 2): the TPU MXU is a fixed 128x128
systolic array — we cannot rewire it.  The paper's *decision surface*
(logical shape x dataflow) maps onto the Pallas schedule:

  logical shape R_l x C_l   -> block tile (bm, bn) (+ depth bk): a tall
                               skinny logical array is a tall skinny
                               output tile; the MXU processes it in
                               ceil(bm/128) x ceil(bn/128) passes without
                               padding the *workload* to a square.
  OS (output stationary)    -> grid (m, n, k), k innermost; the output
                               tile lives in a VMEM scratch accumulator
                               across the whole K-reduction and is written
                               to HBM once (no edge accumulators; exactly
                               the paper's "OS needs no accumulators").
  WS (weight stationary)    -> grid (n, k, m), m innermost; the weight
                               block's index map ignores m so the (bk, bn)
                               weight tile stays VMEM-resident across the
                               M sweep (the preloaded stationary operand);
                               partial outputs stream through HBM via an
                               input/output-aliased accumulator (the
                               paper's edge accumulators in the multi-mode
                               buffer).
  IS (input stationary)     -> grid (m, k, n), n innermost; the (bm, bk)
                               input tile is the resident operand and
                               partial outputs stream, symmetrical to WS.

All three compute identical results (tests sweep dataflows x shapes x
dtypes against kernels/ref.py); they differ in which operand is revisited
from VMEM and which traffic hits HBM — the same trade-off the ReDas
multi-mode buffer manages on the ASIC.

VMEM discipline: one (bm, bk) + one (bk, bn) + one (bm, bn) f32 block
(x2 for the pipeline's double buffering) must fit the ~16 MiB of a v5e
core; `vmem_bytes()` exposes the footprint and ops.py enforces it — the
Pallas realization of the paper's Eq. (2) buffer constraint.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DataflowName = Literal["os", "ws", "is"]

# TPU v5e tiling floor for f32/bf16 operands: (sublane, lane).
SUBLANE = 8
LANE = 128
VMEM_BYTES = 16 * 2**20  # per-core VMEM (v5e)


def _check_block(name: str, b0: int, b1: int) -> None:
    if b0 % SUBLANE or b1 % LANE:
        raise ValueError(
            f"{name} block ({b0}, {b1}) must be multiples of ({SUBLANE}, {LANE}) "
            "for MXU/VREG alignment")


def vmem_bytes(bm: int, bk: int, bn: int, in_dtype=jnp.bfloat16) -> int:
    """VMEM working set of one grid step (x2 double buffering), Eq. 2 analogue."""
    w = jnp.dtype(in_dtype).itemsize
    return 2 * (bm * bk * w + bk * bn * w) + bm * bn * 4  # acc always f32


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def default_blocks(m: int, k: int, n: int,
                   in_dtype=jnp.bfloat16) -> tuple[int, int, int]:
    """Hardware-aligned blocks no larger than the (padded) problem, capped
    so the double-buffered working set fits VMEM (Eq. 2 analogue).  The
    single block-selection policy shared by the dense path
    (engine.backends.pallas_gemm) and the grouped path
    (grouped_gemm.default_group_blocks)."""
    bm = min(round_up(m, SUBLANE), 256)
    bk = min(round_up(k, LANE), 256)
    bn = min(round_up(n, LANE), 256)
    while vmem_bytes(bm, bk, bn, in_dtype) > VMEM_BYTES:  # pragma: no cover
        bk = max(LANE, bk // 2)
    return bm, bk, bn


def _mac(a_ref, b_ref):
    return jnp.dot(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# OS: k innermost, VMEM scratch accumulator, single HBM write per out tile.
# --------------------------------------------------------------------------


def _os_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mac(a_ref, b_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# --------------------------------------------------------------------------
# WS / IS: stationary operand's index map ignores the innermost grid axis
# (stays VMEM-resident); partials stream through the aliased accumulator.
# --------------------------------------------------------------------------


def _streaming_kernel(a_ref, b_ref, acc_ref, o_ref):
    o_ref[...] = acc_ref[...] + _mac(a_ref, b_ref)


def _compiler_params(n_axes: int):
    # Revisited output blocks require sequential ("arbitrary") grid axes.
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * n_axes)


@functools.partial(
    jax.jit, static_argnames=("dataflow", "bm", "bk", "bn", "interpret", "out_dtype"))
def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    dataflow: DataflowName = "os",
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Tiled (M, K) @ (K, N); dims must be multiples of the block dims
    (engine.backends.pallas_gemm pads arbitrary shapes).  Accumulates in f32."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"GEMM dim mismatch {a.shape} @ {b.shape}")
    if m % bm or k % bk or n % bn:
        raise ValueError(f"({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})")
    _check_block("A", bm, bk)
    _check_block("B", bk, bn)
    _check_block("O", bm, bn)
    gm, gk, gn = m // bm, k // bk, n // bn

    a_bs = lambda im: pl.BlockSpec((bm, bk), im)
    b_bs = lambda im: pl.BlockSpec((bk, bn), im)
    o_bs = lambda im: pl.BlockSpec((bm, bn), im)

    if dataflow == "os":
        grid = (gm, gn, gk)
        return pl.pallas_call(
            functools.partial(_os_kernel, n_k=gk),
            grid=grid,
            in_specs=[a_bs(lambda i, j, kk: (i, kk)), b_bs(lambda i, j, kk: (kk, j))],
            out_specs=o_bs(lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=_compiler_params(3),
            interpret=interpret,
        )(a, b)

    # Streaming dataflows: one pallas_call per K-chunk.  Within a call the
    # stationary operand's block index ignores the innermost grid axis, so
    # it stays VMEM-resident across the whole sweep; partial outputs stream
    # through HBM between calls via XLA-level input/output aliasing (each
    # out block is written exactly once per call, so revisit semantics
    # never arise).  On TPU the gk sequential calls are each fully
    # pipelined and XLA elides accumulator copies (donation).
    if dataflow == "ws":
        grid = (gn, gm)  # weight block (0, j) constant across inner i sweep
        in_specs = [
            a_bs(lambda j, i: (i, 0)),
            b_bs(lambda j, i: (0, j)),
            o_bs(lambda j, i: (i, j)),
        ]
        out_spec = o_bs(lambda j, i: (i, j))
    elif dataflow == "is":
        grid = (gm, gn)  # input block (i, 0) constant across inner j sweep
        in_specs = [
            a_bs(lambda i, j: (i, 0)),
            b_bs(lambda i, j: (0, j)),
            o_bs(lambda i, j: (i, j)),
        ]
        out_spec = o_bs(lambda i, j: (i, j))
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    step = pl.pallas_call(
        _streaming_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params(2),
        interpret=interpret,
    )

    def body(kk, acc):
        a_k = jax.lax.dynamic_slice(a, (0, kk * bk), (m, bk))
        b_k = jax.lax.dynamic_slice(b, (kk * bk, 0), (bk, n))
        return step(a_k, b_k, acc)

    out_f32 = jax.lax.fori_loop(0, gk, body, jnp.zeros((m, n), jnp.float32))
    return out_f32.astype(out_dtype)


def register_into(registry) -> None:
    """Register the ReDas GEMM as the `gemm` op of both Pallas backends
    (repro.engine.KernelRegistry)."""
    from repro.engine.backends import _gemm_backend  # lazy: avoids cycle

    registry.register("pallas-tpu", "gemm", _gemm_backend(interpret=False))
    registry.register("pallas-interpret", "gemm", _gemm_backend(interpret=True))
