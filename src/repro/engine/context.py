"""The Engine: decide (cost model + plan cache) then execute (registry).

This is the single entry point the models route matmuls through
(it replaced the pre-engine `layers.USE_REDAS_KERNEL` global + direct
per-op dispatch, both long gone):

    from repro import engine

    with engine.use_engine():                  # mapper-planned Pallas
        logits, _ = transformer.forward(params, cfg, tokens)

    plan = engine.plan_arch(arch_cfg, seq_len=512)   # plan ahead of time
    plan.save("plan.json")                           # serve warm-start

    with engine.use_engine(backend="xla-einsum"):    # baseline numerics,
        ...                                          # same decision path

Decisions happen at jit-trace time (shapes are static there), exactly
like the old per-shape lru caches — but now every op shares ONE
`ExecutionPlan` cache with hit/miss stats, one `CostModel`, and one
backend namespace.  Everything here is jax-free until the first
dispatch; planning an arch imports only numpy-level code.

Trace-time caveat (same as the old USE_REDAS_KERNEL global): the engine
context is consulted when a function is TRACED, not when a compiled
executable re-runs.  A callable jitted outside `use_engine` and then
called inside it (with the same avals) hits the jit cache and keeps its
XLA-native kernels — jit inside the context, as train_lib/serve_lib do.
"""

from __future__ import annotations

import contextlib
import dataclasses

from .cost import CostModel, TPUModel
from .plan import ExecutionPlan, KernelDecision, KernelRequest
from .registry import KernelRegistry, default_registry

_STACK: list["Engine"] = []

#: backends that execute through the int8 quantization plane (ISSUE 5).
#: Their requests are keyed at in_bytes=1 regardless of the float dtype
#: the arrays arrive in — the kernel quantizes operands before the MXU,
#: so the cost model must size tiles (and the plan cache must key) on
#: the bytes that actually move.
INT8_BACKENDS = ("pallas-tpu-int8", "xla-int8")


def backend_in_bytes(backend: str | None, itemsize: int) -> int:
    """The in_bytes a request dispatched on `backend` is keyed with:
    the operand itemsize, except int8 backends pin it to 1 (precision
    is part of the decision-cache key)."""
    return 1 if backend in INT8_BACKENDS else itemsize


#: backends that execute through the N:M structured-sparsity plane
#: (ISSUE 8).  Their `gemm_sparse` requests carry the storage density
#: in the decision key; everything else dispatches like the float plane.
SPARSE_BACKENDS = ("pallas-tpu-sparse", "xla-sparse")


#: float backend -> its int8 sibling (quantize=True config upgrade).
#: Both Pallas spellings map to "pallas-tpu-int8", which auto-resolves
#: interpret mode off-TPU; int8 names pass through.
_INT8_SIBLING = {
    "xla-einsum": "xla-int8",
    "pallas-tpu": "pallas-tpu-int8",
    "pallas-interpret": "pallas-tpu-int8",
    "pallas-tpu-int8": "pallas-tpu-int8",
    "xla-int8": "xla-int8",
}


#: backend -> its sparse sibling (sparsity=<N:M> config upgrade).  Both
#: Pallas spellings map to "pallas-tpu-sparse" (interpret auto-resolves
#: off-TPU); the int8 names also upgrade — sparse×int8 stores int8
#: values + scales INSIDE the SparseTensor, so the sparse backends
#: subsume the int8 ones when both knobs are set; sparse names pass
#: through.
_SPARSE_SIBLING = {
    "xla-einsum": "xla-sparse",
    "pallas-tpu": "pallas-tpu-sparse",
    "pallas-interpret": "pallas-tpu-sparse",
    "xla-int8": "xla-sparse",
    "pallas-tpu-int8": "pallas-tpu-sparse",
    "pallas-tpu-sparse": "pallas-tpu-sparse",
    "xla-sparse": "xla-sparse",
}


def sparse_sibling(backend: str | None) -> str:
    """The sparse backend a `sparsity="N:M"` Serve/Train config executes
    on instead of `backend`; raises with the known names otherwise.
    `None` resolves per host like `int8_sibling`: the Pallas sparse
    kernel on a TPU, the XLA reference elsewhere."""
    if backend is None:
        import jax  # deferred: config construction must not force jax early

        return ("pallas-tpu-sparse" if jax.default_backend() == "tpu"
                else "xla-sparse")
    sibling = _SPARSE_SIBLING.get(backend)
    if sibling is None:
        raise ValueError(
            f"sparsity cannot upgrade kernel_backend {backend!r} to a "
            f"sparse sibling (known: {sorted(_SPARSE_SIBLING)})")
    return sibling


def int8_sibling(backend: str | None) -> str:
    """The int8 backend a `quantize=True` Serve/Train config executes
    on instead of `backend`; raises with the known names otherwise.
    `None` resolves per host exactly like the float plane's default
    (`Engine._resolve_backend`): the Pallas int8 kernel on a TPU, the
    XLA reference elsewhere (interpret-mode Pallas would crawl on CPU
    serving paths)."""
    if backend is None:
        import jax  # deferred: config construction must not force jax early

        return ("pallas-tpu-int8" if jax.default_backend() == "tpu"
                else "xla-int8")
    sibling = _INT8_SIBLING.get(backend)
    if sibling is None:
        raise ValueError(
            f"quantize=True cannot upgrade kernel_backend {backend!r} to "
            f"an int8 sibling (known: {sorted(_INT8_SIBLING)})")
    return sibling


def _dtype_bytes(x) -> int:
    return int(x.dtype.itemsize)


def _as_arrays(*xs):
    """jax arrays (and tracers) pass through untouched; plain numpy /
    python inputs — which the pre-engine `auto_matmul` accepted via jit
    auto-conversion — are converted so `.aval` keying works."""
    if all(hasattr(x, "aval") for x in xs):
        return xs
    import jax.numpy as jnp

    return tuple(jnp.asarray(x) for x in xs)


class Engine:
    """One (cost model, backend, plan, registry) posture.

    `backend=None` auto-resolves at first dispatch: the cost model's
    `default_backend` if set (the ASIC plane's "simulator"), else
    "pallas-tpu" on a TPU host and "pallas-interpret" elsewhere.
    """

    def __init__(self, cost_model: CostModel | None = None, *,
                 backend: str | None = None,
                 plan: ExecutionPlan | None = None,
                 registry: KernelRegistry | None = None):
        self.cost_model = cost_model if cost_model is not None else TPUModel()
        self._backend = backend
        self.registry = registry if registry is not None else default_registry()
        self.plan = plan if plan is not None else ExecutionPlan(
            cost_model=self.cost_model.name, backend=backend or "auto")
        # Steady-state dispatch memo: raw shape key -> (decision, kernel).
        # The hot path (repeated shape, the common case by construction —
        # Sec. 4.3's decision reuse) costs one tuple build + dict hit; the
        # full KernelRequest/plan/registry machinery runs on miss only
        # (BENCH_PR3 gates the overhead at 5% of a direct kernel call).
        self._memo: dict[tuple, tuple] = {}

    # -- backend resolution ------------------------------------------------

    @property
    def backend(self) -> str:
        if self._backend is None:
            self._backend = self._resolve_backend()
        return self._backend

    def _resolve_backend(self) -> str:
        if getattr(self.cost_model, "default_backend", None):
            return self.cost_model.default_backend
        import jax  # deferred: planning alone must not import jax

        return "pallas-tpu" if jax.default_backend() == "tpu" else "pallas-interpret"

    @property
    def int8(self) -> bool:
        """True when this engine executes on the quantized plane."""
        return self.backend in INT8_BACKENDS

    @property
    def sparse(self) -> bool:
        """True when this engine executes on the structured-sparsity
        plane (`sparse_matmul` is dispatchable)."""
        return self.backend in SPARSE_BACKENDS

    # -- decide ------------------------------------------------------------

    def _rebind(self, request: KernelRequest,
                decision: KernelDecision) -> KernelDecision:
        """Bind a decision to this engine's backend.  ASIC-plane
        schedules (tile dims need not be Pallas/VREG aligned) only
        execute on the simulator backend — fail with intent instead of a
        Mosaic block-alignment error, whether the decision came from a
        warm-start plan or a fresh cost-model search."""
        if decision.backend == self.backend:
            return decision
        if "shape_rows" in dict(decision.meta) and self.backend != "simulator":
            raise ValueError(
                f"decision for {request.key()} was produced by an ASIC "
                f"cost model ({decision.cost_model!r}); its tile dims are "
                f"not Pallas-aligned — re-plan with a TPU cost model for "
                f"backend {self.backend!r}")
        return dataclasses.replace(decision, backend=self.backend)

    def decide(self, request: KernelRequest) -> KernelDecision:
        """Plan-cache lookup, cost-model search on miss."""
        hit = self.plan.lookup(request)
        if hit is not None:
            rebound = self._rebind(request, hit)
            if rebound is not hit:
                # warm-start plan recorded on another host kind: keep the
                # schedule, execute on this engine's backend.
                self.plan.add(request, rebound)
            return rebound
        decision = self._rebind(request, self.cost_model.decide(request))
        self.plan.add(request, decision)
        return decision

    def plan_gemms(self, gemms, *, in_bytes: int = 2,
                   out_bytes: int | None = None) -> "Engine":
        """Warm the plan from a GEMM trace (`core.analytical_model.GEMM`
        or (m, k, n) tuples); repeated shapes dedupe through the cache.
        `in_bytes` must match the serving dtype (2 = bf16, 4 = f32) or
        the runtime requests will miss the warm decisions."""
        out_bytes = out_bytes if out_bytes is not None else in_bytes
        for g in gemms:
            m, k, n = (g.M, g.K, g.N) if hasattr(g, "M") else g
            name = getattr(g, "name", "")
            self.decide(KernelRequest("gemm", m, k, n, in_bytes=in_bytes,
                                      out_bytes=out_bytes, name=name))
        return self

    # -- execute -----------------------------------------------------------

    def _resolve(self, key: tuple, op: str, m: int, k: int, n: int,
                 groups: int, item_bytes: int, *, density: float = 1.0,
                 in_bytes: int | None = None) -> tuple:
        """Miss path: full request -> decide -> registry, then memoize.
        On an int8 backend requests key at in_bytes=1 (the width the
        kernel actually moves in), so the same float shapes plan larger
        tiles and never collide with a full-precision plan entry; the
        OUTPUT stays the float compute width — the int8 kernels rescale
        the int32 accumulator to a float result, and the cost model must
        not undercount that output stream.  `density` keys sparse
        requests apart from dense siblings; `in_bytes` overrides the
        backend rule (sparse×int8 storage moves at 1 byte even though
        the sparse backends are not int8 backends)."""
        req = KernelRequest(op, m, k, n, groups=groups,
                            in_bytes=(in_bytes if in_bytes is not None
                                      else backend_in_bytes(self.backend,
                                                            item_bytes)),
                            out_bytes=item_bytes, density=density)
        dec = self.decide(req)
        entry = (dec, self.registry.get(dec.backend, op))
        self._memo[key] = entry
        return entry

    def matmul(self, a, b, *, out_dtype=None):
        """(M, K) @ (K, N) through the planned schedule for this shape."""
        # .aval (hashable shape+dtype) is the cheapest stable identity on
        # both concrete arrays and tracers — ~30x cheaper than touching
        # .shape/.dtype.name per call (BENCH_PR3 is sensitive to this).
        a, b = _as_arrays(a, b)
        key = ("gemm", a.aval, b.aval)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, a, b, out_dtype=out_dtype)
        m, k = a.shape
        k2, n = b.shape
        if k != k2:
            raise ValueError(f"matmul dim mismatch {a.shape} @ {b.shape}")
        dec, fn = self._resolve(key, "gemm", m, k, n, 1, _dtype_bytes(a))
        return fn(dec, a, b, out_dtype=out_dtype)

    def quant_matmul(self, a, w_q, w_scale, *, out_dtype=None):
        """(M, K) float @ pre-quantized (K, N) int8 weight storage
        (`quant.quantize_params`): dispatches the planned `gemm_w8`
        kernel — activations quantize dynamically inside it, the stored
        weight never materializes in float.  Only int8 backends register
        the op; call sites guard on `engine.int8`."""
        a, w_q, w_scale = _as_arrays(a, w_q, w_scale)
        key = ("gemm_w8", a.aval, w_q.aval)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, a, w_q, w_scale, out_dtype=out_dtype)
        m, k = a.shape
        k2, n = w_q.shape
        if k != k2:
            raise ValueError(f"matmul dim mismatch {a.shape} @ {w_q.shape}")
        dec, fn = self._resolve(key, "gemm_w8", m, k, n, 1, _dtype_bytes(a))
        return fn(dec, a, w_q, w_scale, out_dtype=out_dtype)

    def sparse_matmul(self, a, st, *, out_dtype=None):
        """(M, K) float @ N:M structured-sparse weight storage
        (`sparse.prune_params`): dispatches the planned `gemm_sparse`
        kernel — the compressed values/indices never densify in HBM.
        The request carries the storage density (N/M), so the plan
        never collides with a dense sibling of the same shape;
        sparse×int8 storage (int8 values + scales) keys at in_bytes=1.
        Only sparse backends register the op; call sites guard on
        `engine.sparse`."""
        scale = st.scale
        if scale is None:
            a, v, i = _as_arrays(a, st.values, st.indices)
            s_aval = None
        else:
            a, v, i, scale = _as_arrays(a, st.values, st.indices, scale)
            s_aval = scale.aval
        key = ("gemm_sparse", a.aval, v.aval, i.aval, s_aval, st.n, st.m)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, a, v, i, scale, n_keep=st.n, m_group=st.m,
                      out_dtype=out_dtype)
        m, k = a.shape
        n = v.shape[-1]
        if k != st.k_dense:
            raise ValueError(
                f"sparse matmul dim mismatch {a.shape} @ {st!r}")
        item_bytes = _dtype_bytes(a)
        dec, fn = self._resolve(
            key, "gemm_sparse", m, k, n, 1, item_bytes,
            density=st.n / st.m,
            in_bytes=1 if st.quantized else None)
        return fn(dec, a, v, i, scale, n_keep=st.n, m_group=st.m,
                  out_dtype=out_dtype)

    def grouped_matmul(self, x, w, *, out_dtype=None):
        """x (E, C, D) @ w (E, D, F) -> (E, C, F), per-expert."""
        x, w = _as_arrays(x, w)
        key = ("grouped_gemm", x.aval, w.aval)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, x, w, out_dtype=out_dtype)
        e, c, d = x.shape
        e2, d2, f = w.shape
        if (e, d) != (e2, d2):
            raise ValueError(f"grouped dim mismatch {x.shape} @ {w.shape}")
        dec, fn = self._resolve(key, "grouped_gemm", c, d, f, e,
                                _dtype_bytes(x))
        return fn(dec, x, w, out_dtype=out_dtype)

    def attention(self, q, k, v, *, causal: bool = True, window: int = 0):
        """q (B, H, Sq, D); k/v (B, H, Sk, D) (GQA heads pre-expanded)."""
        q, k, v = _as_arrays(q, k, v)
        key = ("attention", q.aval, k.aval, causal, window)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, q, k, v, causal=causal, window=window)
        b, h, sq, d = q.shape
        sk = k.shape[2]
        dec, fn = self._resolve(key, "attention", sq, d, sk, b * h,
                                _dtype_bytes(q))
        return fn(dec, q, k, v, causal=causal, window=window)

    def paged_attention(self, q, k_pages, v_pages, block_tables, kv_len, *,
                        k_scale=None, v_scale=None):
        """Paged decode attention (DESIGN.md §8): q (B, 1, H, D) over
        pools (P, page, KV, D) addressed through `block_tables`
        (B, n_bt); int8 pools pass their per-row scale pools alongside.
        Keyed like the runtime shape it is: n = the full page span the
        table can address (n_bt * page), groups = B * H."""
        q, k_pages, v_pages, block_tables, kv_len = _as_arrays(
            q, k_pages, v_pages, block_tables, kv_len)
        key = ("paged_attention", q.aval, k_pages.aval, block_tables.aval)
        hit = self._memo.get(key)
        if hit is not None:
            self.plan.hits += 1
            dec, fn = hit
            return fn(dec, q, k_pages, v_pages, block_tables, kv_len,
                      k_scale=k_scale, v_scale=v_scale)
        b, sq, h, d = q.shape
        span = block_tables.shape[1] * k_pages.shape[1]
        dec, fn = self._resolve(key, "paged_attention", sq, d, span, b * h,
                                _dtype_bytes(q))
        return fn(dec, q, k_pages, v_pages, block_tables, kv_len,
                  k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# Context management
# ---------------------------------------------------------------------------


def active_engine() -> Engine | None:
    """The innermost `use_engine` engine, or None (XLA-native path)."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def use_engine(engine: Engine | None = None, *, backend: str | None = None,
               cost_model: CostModel | None = None,
               plan: ExecutionPlan | None = None):
    """Route every `models.layers.dense` / `models.moe` matmul in scope
    through an engine.  Pass an existing `Engine` to share its plan
    across contexts, or kwargs to build a scoped one."""
    if engine is None:
        engine = Engine(cost_model, backend=backend, plan=plan)
    elif backend is not None or cost_model is not None or plan is not None:
        raise ValueError("pass either an engine or engine kwargs, not both")
    _STACK.append(engine)
    try:
        yield engine
    finally:
        _STACK.pop()


_DEFAULT: Engine | None = None


def default_engine() -> Engine:
    """Process-wide engine backing the module-level `matmul` when no
    `use_engine` context is active."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Engine()
    return _DEFAULT


def matmul(a, b, *, out_dtype=None):
    """Module-level sugar: active engine if any, else the default one."""
    eng = active_engine() or default_engine()
    return eng.matmul(a, b, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Ahead-of-time planning over a model's GEMM trace
# ---------------------------------------------------------------------------


def decode_requests(cfg, *, batch: int, dtype_bytes: int = 2,
                    seq: int = 1, quantized_weights: bool = False,
                    sparse_weights: bool = False, density: float = 0.5,
                    out_bytes: int | None = None, paged_pages: int = 0,
                    page_size: int = 0) -> tuple[KernelRequest, ...]:
    """The exact engine requests one `models.transformer.decode_step`
    issues at slot-pool size `batch` (M = batch: one token per slot).

    Unlike `core.workloads.arch_gemms` — the mapper's fused *search*
    view of a prefill pass — these mirror the runtime
    `models.layers.dense` / `models.moe._expert_ffn` calls
    per-projection, so a warm-started serving plan turns first-trace
    decode planning into pure cache lookups (the continuous-batching
    scheduler's decode shapes never change, so this one set covers
    every step it ever takes).  SSM in/out projections and the lm head
    are raw matmuls (not engine-routed) and do not appear.

    `seq > 1` instead describes one ragged ADMIT prefill at that padded
    width (M = batch * seq) — the scheduler's other fixed call shape.

    `quantized_weights=True` mirrors a `quant.quantize_params` server:
    the dense projections dispatch as `gemm_w8` (MoE expert stacks stay
    float grouped GEMMs — quantize_params skips them).
    `sparse_weights=True` mirrors a `sparse.prune_params` server the
    same way: dense projections dispatch as `gemm_sparse` at `density`
    (N/M of the pruning spec; grouped GEMMs stay dense — prune_params
    skips expert stacks too), and combined with `quantized_weights=True`
    the storage is sparse×int8, which the runtime keys at in_bytes=1.
    `out_bytes` (default: `dtype_bytes`) is the OUTPUT width — on an
    int8 posture pass dtype_bytes=1, out_bytes=<compute width>,
    matching how the runtime keys its requests (`Engine._resolve`)."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim_
    nh, nkv = cfg.n_heads, cfg.n_kv
    tokens = batch * seq
    out_b = out_bytes if out_bytes is not None else dtype_bytes
    dense_in, dense_density = dtype_bytes, 1.0
    if sparse_weights:
        dense_op, dense_density = "gemm_sparse", density
        if quantized_weights:
            dense_in = 1  # sparse×int8: values move at one byte
    elif quantized_weights:
        dense_op = "gemm_w8"
    else:
        dense_op = "gemm"
    reqs: list[KernelRequest] = []

    def gemm(m, k, n, name):
        reqs.append(KernelRequest(dense_op, m, k, n, in_bytes=dense_in,
                                  out_bytes=out_b, density=dense_density,
                                  name=name))

    def mlp_reqs(prefix):
        if cfg.moe is not None:
            moe = cfg.moe
            # MoEConfig.capacity is jax-free (models.moe itself is not)
            rows = batch * moe.capacity(seq)  # _expert_ffn: (E, B, C, D)
            for m, k, n, nm in ((rows, d, f, "expert_up"),
                                (rows, f, d, "expert_down")):
                reqs.append(KernelRequest(
                    "grouped_gemm", m, k, n, groups=moe.n_experts,
                    in_bytes=dtype_bytes, out_bytes=out_b,
                    name=f"{prefix}/{nm}"))
        else:
            gemm(tokens, d, f, f"{prefix}/ffn_up")  # wi and wg share a shape
            gemm(tokens, f, d, f"{prefix}/ffn_down")

    for kind in sorted(set(cfg.layer_pattern)):
        if kind in ("attn", "local"):
            gemm(tokens, d, nh * hd, f"{kind}/wq")
            gemm(tokens, d, nkv * hd, f"{kind}/wk")  # wv is the same shape
            gemm(tokens, nh * hd, d, f"{kind}/wo")
            mlp_reqs(kind)
            if kind == "attn" and paged_pages and page_size and seq == 1:
                # paged decode gather-attention: n = the page span one
                # block-table row can address — exactly how the runtime
                # Engine.paged_attention keys its request
                reqs.append(KernelRequest(
                    "paged_attention", seq, hd, paged_pages * page_size,
                    groups=batch * nh, in_bytes=dtype_bytes,
                    out_bytes=out_b, name="attn/paged"))
        elif kind == "rglru":
            w = cfg.rglru_width or d
            gemm(tokens, d, w, "rglru/lin_x")  # lin_y is the same shape
            gemm(tokens, w, w, "rglru/gates")  # w_a and w_x
            gemm(tokens, w, d, "rglru/lin_out")
            mlp_reqs("rglru")
        # "ssm": no engine-routed matmuls in the decode path
    return tuple(reqs)


def plan_arch(cfg, *, seq_len: int | None = None, batch: int = 1,
              cost_model: CostModel | None = None,
              backend: str | None = None,
              dtype_bytes: int = 2,
              decode_batch: int | None = None,
              admit_widths: tuple[int, ...] = (),
              quantized_weights: bool = False,
              sparse_weights: bool = False, sparse_density: float = 0.5,
              paged_pages: int = 0, page_size: int = 0,
              verify_k: int = 0, prefill_chunk: int = 0) -> ExecutionPlan:
    """Plan every GEMM of one `repro.models.config.ArchConfig` prefill
    pass via the `core.workloads.arch_gemms` lowering and return the
    warm `ExecutionPlan` (save it for serve warm-start).  `dtype_bytes`
    is the serving compute dtype width (2 = bf16 default, 4 = f32); on
    an int8 `backend` the requests' INPUT width is forced to 1 (runtime
    requests there key at the quantized width, whatever float dtype the
    arrays carry) while outputs keep the compute width — the int8
    kernels rescale to float results.
    `decode_batch` additionally plans the fixed decode-step shapes for
    a slot pool of that size (see `decode_requests`) so a continuous-
    batching server's decode trace re-plans nothing; `admit_widths`
    does the same for its admit widths (the scheduler's
    `prefill_bucket` multiples), at batch 1 (the contiguous layout's
    per-pick prefill) and at the whole pool (the paged layout's ragged
    prefill and the draft's).  `quantized_weights` plans the decode/
    admit dense projections as `gemm_w8` (a `quant.quantize_params`
    server dispatches those instead of `gemm`); `sparse_weights` plans
    them as `gemm_sparse` at `sparse_density` (a `sparse.prune_params`
    server — both flags together describe sparse×int8 storage, keyed
    at in_bytes=1 like the runtime does).  `paged_pages` /
    `page_size` (a `cache_layout="paged"` server: slot_pages and the
    page size) additionally plan the paged decode gather-attention
    shape, so the paged scheduler's steady state also re-plans
    nothing.  `verify_k` (a `speculate_k=k` server) adds the k+1-wide
    speculative verify width — the only extra decode shape the
    speculative tick introduces (the draft's propose steps are the
    width-1 shapes, its prefill the admit widths; the paged verify
    bypasses the engine's paged_attention op entirely).  `prefill_chunk`
    (a `ServeConfig.prefill_chunk` server, DESIGN.md §12) adds the
    chunk width — every chunked-ingestion call is exactly that wide, so
    it is the ONE extra shape chunking introduces; the scheduler aligns
    the chunk to `prefill_bucket`, so when `admit_widths` covers the
    bucket multiples the chunk width is already planned and this kwarg
    merely makes the posture explicit."""
    from repro.core.workloads import ARCH_TRACE_SEQ, arch_gemms

    in_bytes = backend_in_bytes(backend, dtype_bytes)
    eng = Engine(cost_model, backend=backend)
    eng.backend  # resolve now so the plan records a concrete backend
    eng.plan.backend = eng.backend
    eng.plan_gemms(arch_gemms(cfg, seq_len=seq_len or ARCH_TRACE_SEQ,
                              batch=batch), in_bytes=in_bytes,
                   out_bytes=dtype_bytes)
    if decode_batch:
        widths = (1,) + tuple(admit_widths)
        if prefill_chunk and prefill_chunk not in widths:
            widths = widths + (prefill_chunk,)
        if verify_k:
            widths = widths + (verify_k + 1,)
        shapes = [(decode_batch, w) for w in widths] + \
            [(1, w) for w in admit_widths]
        for b, width in shapes:
            for req in decode_requests(cfg, batch=b,
                                       dtype_bytes=in_bytes, seq=width,
                                       quantized_weights=quantized_weights,
                                       sparse_weights=sparse_weights,
                                       density=sparse_density,
                                       out_bytes=dtype_bytes,
                                       paged_pages=paged_pages,
                                       page_size=page_size):
                eng.decide(req)
    return eng.plan
