"""Grouped (per-expert) Pallas GEMM — the MoE expert-FFN hot spot.

Computes y[e] = x[e] @ w[e] for e in [0, E) with one pallas_call:
grid (E, C/bc, F/bf, D/bd), OS-style VMEM accumulator over the D sweep.
The expert axis is an independent ("parallel") grid dimension, so on EP
meshes each core runs only its local experts' sub-grid — this is the
kernel the sorted-dispatch path (models/moe.py) feeds its (E, C, D)
buffers through on TPU.

For capacity-padded buffers the padded rows multiply zeros (exact).
Validated against kernels/ref.grouped_matmul_ref in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .redas_gemm import VMEM_BYTES, default_blocks, vmem_bytes


def default_group_blocks(c: int, d: int, f: int,
                         in_dtype=jnp.bfloat16) -> tuple[int, int, int]:
    """Per-expert blocks through the shared Eq.-2 VMEM gate — literally
    the dense path's policy (`redas_gemm.default_blocks`) applied to the
    per-group (C, D, F) problem."""
    return default_blocks(c, d, f, in_dtype)


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_d: int):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_d - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bc", "bd", "bf", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, *, bc: int | None = None,
                   bd: int | None = None, bf: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """x (E, C, D) @ w (E, D, F) -> (E, C, F); dims padded to blocks.

    Blocks default through `default_group_blocks` (the shared Eq.-2 VMEM
    gate); explicit blocks that overflow VMEM are rejected like the
    dense path's `pallas_gemm`."""
    e, c, d = x.shape
    _, _, f = w.shape
    dbc, dbd, dbf = default_group_blocks(c, d, f, x.dtype)
    bc, bd, bf = bc or dbc, bd or dbd, bf or dbf
    if vmem_bytes(bc, bd, bf, x.dtype) > VMEM_BYTES:
        raise ValueError(
            f"blocks ({bc},{bd},{bf}) exceed VMEM budget {VMEM_BYTES} (Eq. 2)")
    pad = lambda v, b: -(-v // b) * b
    cp, dp, fp = pad(c, bc), pad(d, bd), pad(f, bf)
    if (cp, dp) != (c, d):
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, dp - d)))
    if (dp, fp) != (d, f):
        w = jnp.pad(w, ((0, 0), (0, dp - d), (0, fp - f)))
    n_d = dp // bd
    out = pl.pallas_call(
        functools.partial(_kernel, n_d=n_d),
        grid=(e, cp // bc, fp // bf, n_d),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ee, i, j, k: (ee, i, k)),
            pl.BlockSpec((1, bd, bf), lambda ee, i, j, k: (ee, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ee, i, j, k: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :c, :f]


@functools.lru_cache(maxsize=None)
def _diff_grouped(bc: int, bd: int, bf: int, interpret: bool):
    """Differentiable wrapper (the kernel itself has no JVP rule): both
    cotangents are grouped GEMMs on transposed operands and run through
    the same kernel with VMEM-gated default blocks."""

    @jax.custom_vjp
    def f(x, w):
        return grouped_matmul(x, w, bc=bc, bd=bd, bf=bf, interpret=interpret)

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dx = grouped_matmul(g, w.transpose(0, 2, 1), interpret=interpret)
        dw = grouped_matmul(x.transpose(0, 2, 1), g, interpret=interpret)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    f.defvjp(fwd, bwd)
    # jit the wrapper: an un-jitted custom_vjp call re-traces eagerly.
    return jax.jit(f)


def register_into(registry) -> None:
    """Register the grouped GEMM as the `grouped_gemm` op of both Pallas
    backends (repro.engine.KernelRegistry)."""
    def _run(interpret: bool):
        def run(decision, x, w, *, out_dtype=None):
            fn = _diff_grouped(decision.bm, decision.bk, decision.bn,
                               interpret)
            out = fn(x, w)
            return out.astype(out_dtype or x.dtype)
        return run

    registry.register("pallas-tpu", "grouped_gemm", _run(interpret=False))
    registry.register("pallas-interpret", "grouped_gemm", _run(interpret=True))
