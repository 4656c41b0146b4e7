"""Batched mapper search engine vs the scalar oracle (the PR-2 gate).

Property tests run under hypothesis with the settings profile that
tests/conftest.py loads.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.accelerators import SPECS
from repro.core.analytical_model import GEMM, LOOP_ORDERS, MappingConfig
from repro.core.dataflow import Dataflow
from repro.core.mapper import _STREAM_DIM, ALLOC_CANDIDATES, ReDasMapper

MODEL = SPECS["redas"].model(128)

gemms = st.builds(GEMM, M=st.integers(1, 2048), K=st.integers(1, 2048),
                  N=st.integers(1, 2048))
tiles = st.integers(1, 4096)


@given(gemms, st.sampled_from(list(Dataflow)),
       st.sampled_from(SPECS["redas"].shapes), tiles, tiles, tiles,
       st.integers(0, len(LOOP_ORDERS) - 1),
       st.integers(0, len(ALLOC_CANDIDATES) - 1))
@settings(max_examples=80, deadline=None)
def test_batched_cost_matches_scalar_on_random_candidates(
        g, df, shape, tm, tk, tn, oid, aid):
    """estimate_batch == estimate bit-for-bit on arbitrary candidates,
    including invalid ones (inf) — the shared-kernel contract."""
    cfg = MappingConfig(dataflow=df, shape=shape, tile_m=tm, tile_k=tk,
                        tile_n=tn, loop_order=LOOP_ORDERS[oid],
                        alloc=ALLOC_CANDIDATES[aid])
    rep = MODEL.estimate(g, cfg)
    res = MODEL.estimate_batch(
        g,
        rows=np.array([shape.rows]), cols=np.array([shape.cols]),
        tile_m=np.array([tm]), tile_k=np.array([tk]), tile_n=np.array([tn]),
        order_ids=np.array([oid]),
        stream_dims=np.array([_STREAM_DIM[df]]),
        alloc=np.array([ALLOC_CANDIDATES[aid]]))
    assert bool(res["valid"][0]) == rep.valid
    want = rep.cycles if rep.valid else float("inf")
    assert res["cycles"][0] == want


@given(gemms)
@settings(max_examples=10, deadline=None)
def test_batched_search_picks_scalar_oracle_decision(g):
    batched = ReDasMapper(SPECS["redas"]).map_gemm(g)
    scalar = ReDasMapper(SPECS["redas"], vectorized=False).map_gemm(g)
    assert batched.config == scalar.config
    assert batched.report.cycles == scalar.report.cycles
    assert batched.candidates_evaluated == scalar.candidates_evaluated


def test_candidate_batch_mirrors_generator_order():
    g = GEMM(784, 256, 128)
    mapper = ReDasMapper(SPECS["redas"])
    batch = mapper.candidate_batch(g)
    cands = list(mapper.candidates(g))
    assert len(batch) == len(cands)
    step = max(1, len(cands) // 97)  # spot-check a spread of rows
    for i in range(0, len(cands), step):
        assert batch.config(i) == cands[i]


def test_all_specs_agree_on_headline_gemm():
    g = GEMM(43264, 144, 32)  # the Fig. 22 case-study layer
    for name in ("tpu", "gemmini", "planaria", "dynnamic", "sara", "redas"):
        b = ReDasMapper(SPECS[name]).map_gemm(g)
        s = ReDasMapper(SPECS[name], vectorized=False).map_gemm(g)
        assert b.config == s.config, name
        assert b.report == s.report, name


def test_decision_cache_returns_identical_objects():
    mapper = ReDasMapper(SPECS["redas"])
    first = mapper.map_gemm(GEMM(784, 256, 128))
    second = mapper.map_gemm(GEMM(784, 256, 128))
    assert second.config is first.config  # cached object, not a re-search
    assert second.candidates_evaluated == 0
    counted = mapper.map_gemm(GEMM(784, 256, 128, count=5))
    assert counted.config is first.config
    assert counted.report.cycles > first.report.cycles  # count-scaled


def test_arch_traces_map_cleanly():
    """Every registered arch config lowers to GEMMs the engine can map."""
    from repro.core.workloads import arch_traces

    mapper = ReDasMapper(SPECS["redas"])  # shared decision cache across archs
    for name, gemms in arch_traces(smoke=True, seq_len=64).items():
        assert gemms, name
        mapping = mapper.map_model(gemms)
        assert mapping.total_cycles > 0, name


def test_arch_trace_tolerates_truncated_layer_pattern():
    """n_layers shorter than the pattern period leaves some block kinds
    with zero instances; they are skipped, not emitted as count=0."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.workloads import arch_gemms

    cfg = dataclasses.replace(get_config("recurrentgemma-2b"), n_layers=1)
    gemms = arch_gemms(cfg, seq_len=64)
    assert gemms and all(g.count >= 1 for g in gemms)
