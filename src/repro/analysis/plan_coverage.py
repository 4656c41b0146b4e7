"""Pass 2: plan coverage — zero steady-state misses as a theorem.

BENCH_PR4/PR6/PR7/PR8 prove empirically that a warm-started scheduler
re-plans nothing (`steady_state_new_misses == 0`).  This pass proves the
same set-inclusion statically: for every serving surface a
`ServeConfig` can express (cache layout x quantize x sparsity x
speculate_k x decode batch), the shapes the continuous-batching
scheduler can request at runtime — derived here INDEPENDENTLY of
`engine.decode_requests`, from the scheduler's own admission rules —
must all be pre-declared by `plan_arch`.

  PC001  a runtime-reachable request the plan does not hold (a removed
         verify_k width, a dropped admit bucket, a forgotten paged
         gather shape... the first trace would re-search mid-serve).
  PC002  a surface combination that fails to plan at all.

The runtime shape mirror follows the scheduler contract:
  * decode ticks are width 1 at the full slot pool;
  * admits prefill at `ceil(maxlen / prefill_bucket) * prefill_bucket`
    capped at max_seq — so every bucket multiple up to max_seq is
    reachable, at the full slot pool (paged layout, chunks, the draft)
    and, on the contiguous layout, at batch 1 (one call per pick);
  * a `speculate_k=k` server adds exactly the fused k+1 verify width;
  * a paged server adds the gather-attention shape spanning
    `slot_pages * page_size` addressable rows;
and the scheduler's own refusals (encoder archs, embedding frontends,
a verify window overflowing a sliding-window ring) prune unreachable
surfaces rather than demanding coverage for them.
"""

from __future__ import annotations

import dataclasses
import os

from . import Finding, is_real_root, rel
from ._astutil import def_line

#: reference serving posture the coverage proof runs at.  The widths /
#: pool sizes are small (fast to plan) but structurally complete: a
#: multi-bucket admit ladder, a non-trivial page table, a k+1 verify
#: width that differs from every admit width.
BATCH = 4
MAX_SEQ = 64
PREFILL_BUCKET = 16
PAGE_SIZE = 16
SPECULATE_K = 2
#: chunked-ingestion width (DESIGN.md §12).  The Scheduler aligns the
#: chunk to the prefill bucket, so every legal chunk width is already a
#: member of the admit-width ladder — passing it to plan_arch makes the
#: posture explicit without adding a shape (the proof would catch a
#: future chunk width escaping the ladder).
PREFILL_CHUNK = 32
SEED_BACKEND = "pallas-tpu"


@dataclasses.dataclass(frozen=True)
class Surface:
    """One ServeConfig posture (the runtime-visible axes only)."""

    layout: str            # "contiguous" | "paged"
    quantize: bool
    sparse: bool
    speculate_k: int

    def label(self) -> str:
        bits = [self.layout]
        if self.quantize:
            bits.append("int8")
        if self.sparse:
            bits.append("2:4")
        if self.speculate_k:
            bits.append(f"spec_k={self.speculate_k}")
        return "+".join(bits)


def servable(cfg) -> bool:
    """Mirror of the Scheduler constructor's arch guards."""
    return (cfg.kind != "encoder" and not cfg.embed_inputs
            and not cfg.prefix_tokens)


def surfaces(cfg):
    """Every Surface the scheduler would accept for this arch."""
    layouts = ["contiguous"]
    if "attn" in cfg.layer_pattern:
        # a paged ServeConfig on an attention-free arch arms no paged
        # plane (Scheduler leaves self.paged None) — same shapes as
        # contiguous, so only attention archs add the paged surface.
        layouts.append("paged")
    for layout in layouts:
        for quantize in (False, True):
            for sparse in (False, True):
                for k in (0, SPECULATE_K):
                    if k and "local" in cfg.layer_pattern:
                        ring = min(cfg.window, MAX_SEQ)
                        if k + 1 > ring:
                            continue  # the Scheduler refuses this combo
                    yield Surface(layout, quantize, sparse, k)


def backend_for(surface: Surface) -> str:
    """Mirror the ServeConfig.__post_init__ backend upgrade chain using
    the real sibling maps (explicit seed: no jax-importing None path)."""
    from repro.engine.context import int8_sibling, sparse_sibling

    backend = SEED_BACKEND
    if surface.quantize:
        backend = int8_sibling(backend)
    if surface.sparse:
        backend = sparse_sibling(backend)
    return backend


def admit_widths() -> tuple[int, ...]:
    """Every admit width `_prefill_group` can compute: bucket multiples
    of maxlen in [1, max_seq], capped at max_seq."""
    widths = sorted({min(-(-maxlen // PREFILL_BUCKET) * PREFILL_BUCKET,
                         MAX_SEQ)
                     for maxlen in range(1, MAX_SEQ + 1)})
    return tuple(widths)


def expected_requests(cfg, surface: Surface):
    """The KernelRequests a steady-state scheduler can issue on this
    surface — derived from the arch + scheduler contract, NOT from
    `engine.decode_requests` (this is the independent re-derivation the
    coverage proof needs; tests pin the two against each other)."""
    from repro.engine.context import backend_in_bytes
    from repro.engine.plan import KernelRequest

    backend = backend_for(surface)
    plan_bytes = backend_in_bytes(backend, 2)
    out_b = 2
    if surface.sparse:
        dense_op, density = "gemm_sparse", 0.5
        dense_in = 1 if surface.quantize else plan_bytes
    elif surface.quantize:
        dense_op, density, dense_in = "gemm_w8", 1.0, plan_bytes
    else:
        dense_op, density, dense_in = "gemm", 1.0, plan_bytes

    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim_
    nh, nkv = cfg.n_heads, cfg.n_kv
    widths = (1,) + admit_widths()
    if surface.speculate_k:
        widths = widths + (surface.speculate_k + 1,)

    reqs = []

    def dense(m, k, n, label):
        reqs.append((KernelRequest(dense_op, m, k, n, in_bytes=dense_in,
                                   out_bytes=out_b, density=density), label))

    shapes = [(BATCH, w) for w in sorted(set(widths))]
    if surface.layout == "contiguous":
        shapes += [(1, w) for w in admit_widths()]
    for b, width in shapes:
        tokens = b * width
        tag = f"w={width}" + ("" if b == BATCH else f" b={b}")
        for kind in sorted(set(cfg.layer_pattern)):
            if kind in ("attn", "local"):
                dense(tokens, d, nh * hd, f"{kind}/q {tag}")
                dense(tokens, d, nkv * hd, f"{kind}/kv {tag}")
                dense(tokens, nh * hd, d, f"{kind}/o {tag}")
            elif kind == "rglru":
                w = cfg.rglru_width or d
                dense(tokens, d, w, f"rglru/in {tag}")
                dense(tokens, w, w, f"rglru/gate {tag}")
                dense(tokens, w, d, f"rglru/out {tag}")
            elif kind == "ssm":
                continue  # raw matmuls, not engine-routed
            if cfg.moe is not None:
                rows = b * cfg.moe.capacity(width)
                for m, k, n in ((rows, d, f), (rows, f, d)):
                    reqs.append((KernelRequest(
                        "grouped_gemm", m, k, n, groups=cfg.moe.n_experts,
                        in_bytes=plan_bytes, out_bytes=out_b),
                        f"{kind}/expert {tag}"))
            else:
                dense(tokens, d, f, f"{kind}/ffn_up {tag}")
                dense(tokens, f, d, f"{kind}/ffn_down {tag}")
    if surface.layout == "paged" and "attn" in cfg.layer_pattern:
        slot_pages = -(-MAX_SEQ // PAGE_SIZE)
        reqs.append((KernelRequest(
            "paged_attention", 1, hd, slot_pages * PAGE_SIZE,
            groups=BATCH * nh, in_bytes=plan_bytes, out_bytes=out_b),
            "attn/paged-gather"))
    return reqs


def build_plan(cfg, surface: Surface):
    """The plan a serving harness would warm-start this surface from."""
    from repro.engine.context import plan_arch

    slot_pages = -(-MAX_SEQ // PAGE_SIZE)
    return plan_arch(
        cfg, backend=backend_for(surface), decode_batch=BATCH,
        admit_widths=admit_widths(),
        quantized_weights=surface.quantize,
        sparse_weights=surface.sparse, sparse_density=0.5,
        paged_pages=slot_pages if surface.layout == "paged" else 0,
        page_size=PAGE_SIZE if surface.layout == "paged" else 0,
        verify_k=surface.speculate_k, prefill_chunk=PREFILL_CHUNK)


def check_plan(cfg, surface: Surface, plan, *, file: str, line: int
               ) -> list[Finding]:
    """PC001 for every runtime-reachable request `plan` cannot answer."""
    findings = []
    for req, label in expected_requests(cfg, surface):
        if plan.decisions.get(req.key()) is None:
            findings.append(Finding(
                "PC001", file, line, cfg.name,
                f"[{surface.label()}] runtime shape {label} "
                f"{req.key()} is not in the warm plan — the scheduler "
                f"would re-search mid-serve (steady-state miss)"))
    return findings


def run(root: str) -> list[Finding]:
    if not is_real_root(root):
        return []  # dynamic pass: needs the importable planning plane
    from repro.configs import all_configs

    ctx = os.path.join(root, "engine", "context.py")
    file, line = rel(ctx), def_line(ctx, "plan_arch")
    findings: list[Finding] = []
    for cfg in all_configs().values():
        if not servable(cfg):
            continue
        for surface in surfaces(cfg):
            try:
                plan = build_plan(cfg, surface)
            except Exception as e:  # noqa: BLE001 - any failure is the finding
                findings.append(Finding(
                    "PC002", file, line, cfg.name,
                    f"[{surface.label()}] plan_arch failed: {e}"))
                continue
            findings.extend(check_plan(cfg, surface, plan,
                                       file=file, line=line))
    return findings
