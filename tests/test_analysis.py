"""Tests for `repro.analysis` — the static invariant checker.

Three layers:

* planted fixtures: each check id fires on its fixture tree at the
  planted file:line, and the matching clean fixture stays silent;
* in-process plants for the dynamic passes (a verify width removed
  from a real plan, an orphan param leaf injected into the classifier);
* drift tests: every stdlib mirror inside the analyzer (int8/sparse
  executed-block derivations, the param/cache leaf trees, `_auto_spec`)
  is pinned against the real jax implementation it mirrors, so the
  jax-free analysis cannot silently diverge from what executes.
"""

import os
import subprocess
import sys

import jax
import pytest

from repro import analysis
from repro.analysis import kernel_legality as KL
from repro.analysis import plan_coverage as PC
from repro.analysis import sharding_rules as SH
from repro.configs import all_configs, get_config

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
SRC = os.path.dirname(analysis.REAL_ROOT)


def run_pass(pass_name: str, fixture: str):
    return analysis.run_passes(root=os.path.join(FIXTURES, fixture),
                               passes=(pass_name,))


# ---------------------------------------------------------------------------
# The CLI contract: clean tree, exit 0, jax-free
# ---------------------------------------------------------------------------


def test_cli_clean_tree_exits_zero_and_never_imports_jax():
    code = ("import sys\n"
            "import repro.analysis.__main__ as m\n"
            "rc = m.main([])\n"
            "assert 'jax' not in sys.modules, 'analysis imported jax'\n"
            "sys.exit(rc)\n")
    env = {**os.environ, "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_cli_nonzero_with_file_line_on_planted_fixture():
    root = os.path.join(FIXTURES, "bad_ladder")
    code = ("import sys\n"
            "import repro.analysis.__main__ as m\n"
            f"sys.exit(m.main(['--root', {root!r}, "
            "'--passes', 'kernel-legality', '--allowlist', '-']))\n")
    env = {**os.environ, "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 1, r.stdout + r.stderr
    # the planted _ladder(m, 4, 512) call sits on line 14 of the fixture
    assert "core/tpu_model.py:14: KL002" in r.stdout


def test_unknown_pass_is_an_error():
    with pytest.raises(ValueError, match="unknown pass"):
        analysis.run_passes(passes=("no-such-pass",))


def test_allowlist_rejects_missing_justification(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text("KL002 src/x.py::f -- \n")
    with pytest.raises(ValueError, match="justification"):
        analysis.load_allowlist(str(p))


def test_committed_allowlist_parses_and_every_entry_is_used():
    allow = analysis.load_allowlist()
    assert allow  # the burn-down left intentional entries behind
    idents = {f.ident for f in analysis.run_passes()}
    assert set(allow) <= idents, f"stale entries: {set(allow) - idents}"
    assert not (idents - set(allow)), \
        f"unsuppressed findings: {idents - set(allow)}"


# ---------------------------------------------------------------------------
# Kernel legality: planted fixtures
# ---------------------------------------------------------------------------


def test_kl002_fires_on_misaligned_ladder():
    found = [f for f in run_pass("kernel-legality", "bad_ladder")
             if f.check_id == "KL002"]
    assert len(found) == 1
    f = found[0]
    assert f.file.endswith("core/tpu_model.py") and f.line == 14
    assert "align=4" in f.message and "SUBLANE" in f.message


def test_clean_ladder_fixture_is_silent():
    assert run_pass("kernel-legality", "clean_ladder") == []


def test_kl005_kl006_fire_on_planted_grids():
    found = run_pass("kernel-legality", "bad_grid")
    by_id = {f.check_id: f for f in found}
    assert set(by_id) == {"KL005", "KL006"}
    assert by_id["KL005"].symbol == "bad_arity"
    assert by_id["KL005"].line == 16  # the 1-arg lambda
    assert by_id["KL006"].symbol == "bad_return"
    assert by_id["KL006"].line == 27  # the 3-tuple lambda


def test_clean_grid_fixture_is_silent():
    assert run_pass("kernel-legality", "clean_grid") == []


def test_kl001_fires_on_unknown_op():
    found = [f for f in run_pass("kernel-legality", "bad_registry")
             if f.check_id == "KL001"]
    assert len(found) == 1
    assert "gemm_typo" in found[0].message
    assert found[0].file.endswith("kernels/reg.py") and found[0].line == 10


# ---------------------------------------------------------------------------
# Plan coverage: in-process plants against a real plan
# ---------------------------------------------------------------------------


def _coverage_cfg():
    for cfg in all_configs().values():
        if PC.servable(cfg) and "attn" in cfg.layer_pattern \
                and cfg.moe is None:
            return cfg
    raise AssertionError("no plain attention config")


def test_pc001_catches_removed_verify_width():
    cfg = _coverage_cfg()
    surface = PC.Surface("contiguous", False, False, PC.SPECULATE_K)
    plan = PC.build_plan(cfg, surface)
    assert PC.check_plan(cfg, surface, plan, file="f", line=1) == []
    verify_m = PC.BATCH * (PC.SPECULATE_K + 1)
    kept = {k: v for k, v in plan.decisions.items() if k[1] != verify_m}
    assert len(kept) < len(plan.decisions)  # the width was actually planned
    plan.decisions.clear()
    plan.decisions.update(kept)
    found = PC.check_plan(cfg, surface, plan, file="f", line=1)
    assert found and all(f.check_id == "PC001" for f in found)
    assert any(f"w={PC.SPECULATE_K + 1}" in f.message for f in found)


def test_pc001_catches_removed_admit_bucket():
    cfg = _coverage_cfg()
    surface = PC.Surface("contiguous", False, False, 0)
    plan = PC.build_plan(cfg, surface)
    bucket_m = PC.BATCH * PC.admit_widths()[0]
    kept = {k: v for k, v in plan.decisions.items() if k[1] != bucket_m}
    assert len(kept) < len(plan.decisions)
    plan.decisions.clear()
    plan.decisions.update(kept)
    found = PC.check_plan(cfg, surface, plan, file="f", line=1)
    assert found and all(f.check_id == "PC001" for f in found)
    assert any(f"w={PC.admit_widths()[0]}" in f.message for f in found)


def test_paged_surface_requires_the_gather_shape():
    cfg = _coverage_cfg()
    surface = PC.Surface("paged", False, False, 0)
    plan = PC.build_plan(cfg, surface)
    assert PC.check_plan(cfg, surface, plan, file="f", line=1) == []
    kept = {k: v for k, v in plan.decisions.items()
            if k[0] != "paged_attention"}
    assert len(kept) < len(plan.decisions)
    plan.decisions.clear()
    plan.decisions.update(kept)
    found = PC.check_plan(cfg, surface, plan, file="f", line=1)
    assert [f.check_id for f in found] == ["PC001"]
    assert "paged-gather" in found[0].message


# ---------------------------------------------------------------------------
# Sharding rules: planted cache table + injected param leaves
# ---------------------------------------------------------------------------


def test_cache_table_plants_each_fire_once():
    found = [f for f in run_pass("sharding-rules", "bad_cache_axes")
             if f.check_id.startswith("SH00") and f.check_id != "SH006"]
    by_id = {}
    for f in found:
        by_id.setdefault(f.check_id, []).append(f)
    assert set(by_id) == {"SH001", "SH002", "SH003", "SH007"}
    assert [f.symbol for f in by_id["SH001"]] == ["conv"]
    assert [f.symbol for f in by_id["SH002"]] == ["cells"]
    assert [f.symbol for f in by_id["SH003"]] == ["state"]
    assert [f.symbol for f in by_id["SH007"]] == ["h"]
    # findings anchor to the planted table lines in the fixture
    assert by_id["SH003"][0].line == 25
    assert by_id["SH007"][0].line == 27


def test_sh004_orphan_param_leaf():
    found = SH.check_param_leaves(
        [("stack/b0/weird", (2, 8, 16, 32))], file="f", line=1, arch="x")
    assert [f.check_id for f in found] == ["SH004"]
    assert "weird" in found[0].message


def test_sh005_ambiguous_param_leaf():
    found = SH.check_param_leaves(
        [("moe/experts/embed", (4, 8, 16))], file="f", line=1, arch="x")
    assert [f.check_id for f in found] == ["SH005"]
    assert "embed" in found[0].message and "experts" in found[0].message


def test_sh006_fully_replicated_matmul_leaf():
    found = SH.check_param_leaves(
        [("mlp/wi/w", (15, 33))], file="f", line=1, arch="x")
    assert [f.check_id for f in found] == ["SH006"]


# ---------------------------------------------------------------------------
# Jit discipline: planted fixtures
# ---------------------------------------------------------------------------


def test_jit_plants_each_fire_once():
    found = run_pass("jit-discipline", "bad_jit")
    by_id = {}
    for f in found:
        by_id.setdefault(f.check_id, []).append(f)
    assert set(by_id) == {"JD001", "JD002", "JD003"}
    assert by_id["JD001"][0].symbol == "make_step"
    assert by_id["JD001"][0].line == 12
    assert by_id["JD002"][0].symbol == "forward"
    assert by_id["JD002"][0].line == 16
    assert by_id["JD003"][0].symbol == "apply"


def test_clean_jit_fixture_is_silent():
    assert run_pass("jit-discipline", "clean_jit") == []


# ---------------------------------------------------------------------------
# Docs consistency: planted fixtures + the real tree staying clean
# ---------------------------------------------------------------------------


def test_docs_plants_each_fire_once():
    found = run_pass("docs-consistency", "bad_docs")
    by_id = {}
    for f in found:
        by_id.setdefault(f.check_id, []).append(f)
    assert set(by_id) == {"DC001", "DC002", "DC003"}
    # DC001: the stale README citation and the stale docstring citation
    assert [(f.symbol, f.line) for f in by_id["DC001"]] == [
        ("§99", 5), ("§77", 3)]
    assert by_id["DC001"][1].file.endswith("goodpkg/mod.py")
    # DC002: the undocumented package, anchored to the module-map header
    assert [(f.symbol, f.line) for f in by_id["DC002"]] == [
        ("mysteryplane", 7)]
    # DC003: one dead path ref + one dead dotted ref, at the planted lines
    assert [(f.symbol, f.line) for f in by_id["DC003"]] == [
        ("goodpkg/gone.py", 13), ("repro.goodpkg.vanished", 14)]
    # the "is removed" paragraph is exempt — documenting a removal is fine
    assert not any("olde" in f.message for f in found)


def test_clean_docs_fixture_is_silent():
    assert run_pass("docs-consistency", "clean_docs") == []


def test_real_docs_have_no_stale_findings():
    """Acceptance gate: stale-doc findings are burned down in the docs,
    never allowlisted — the DC pass must be clean on the real tree."""
    found = analysis.run_passes(passes=("docs-consistency",))
    assert found == [], "\n".join(f.text() for f in found)


# ---------------------------------------------------------------------------
# Drift tests: the stdlib mirrors vs the real jax implementations
# ---------------------------------------------------------------------------


def test_int8_block_mirror_matches_kernel():
    from repro.kernels.quant_gemm import align_int8_blocks

    for triple in [(8, 128, 128), (32, 256, 128), (64, 512, 256),
                   (256, 2048, 512), (96, 1024, 384), (512, 1536, 512)]:
        assert KL.mirror_align_int8(*triple) == align_int8_blocks(*triple), \
            triple


def test_sparse_block_mirror_matches_kernel():
    from repro.kernels.sparse_gemm import default_sparse_blocks

    for m, k_dense, n in [(1, 512, 512), (4, 896, 896), (64, 2048, 2048),
                          (128, 8960, 1536), (12, 4864, 1536),
                          (256, 11008, 4096)]:
        for n_keep, m_group in ((2, 4), (1, 4), (4, 8)):
            got = KL.mirror_sparse_blocks(m, k_dense, n, n_keep, m_group)
            want = default_sparse_blocks(m, k_dense, n, n_keep, m_group)
            assert got == want, (m, k_dense, n, n_keep, m_group)


def _leaf_paths(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(getattr(p, "name", p)))
        out["/".join(parts)] = tuple(leaf.shape)
    return out


@pytest.mark.parametrize("arch", sorted(all_configs()))
def test_param_leaf_mirror_matches_init_params(arch):
    from repro.models import transformer as T

    cfg = get_config(arch, smoke=True)
    real = _leaf_paths(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    mirror = dict(SH.param_leaves(cfg))
    assert real == mirror, (
        f"{arch}: only-real {sorted(set(real) - set(mirror))[:5]} "
        f"only-mirror {sorted(set(mirror) - set(real))[:5]}")


@pytest.mark.parametrize("arch", sorted(all_configs()))
def test_cache_leaf_mirror_matches_slot_cache_shape(arch):
    import jax.numpy as jnp

    from repro.models import transformer as T

    cfg = get_config(arch, smoke=True)
    kinds = set(cfg.layer_pattern)
    int8_ok = bool(kinds & {"attn", "local"})
    for paged in (False, True) if "attn" in kinds else (False,):
        for int8 in (False, True) if int8_ok else (False,):
            spec = T.CacheSpec(
                max_seq=32, batch=2,
                page_size=16 if paged else None,
                n_pages=8 if paged else None)
            dtype = jnp.int8 if int8 else jnp.bfloat16
            mirror: dict[str, int] = {}
            for kind in sorted(kinds):
                real = jax.eval_shape(
                    lambda k=kind: T._slot_cache_shape(k, cfg, spec, dtype))
                got = {name: len(leaf.shape) for name, leaf in real.items()}
                want = {
                    name: nd for name, nd in SH.cache_slot_leaves(
                        cfg, paged=paged, int8=int8).items()
                    if name in _kind_leaves(kind, paged, int8)}
                assert got == want, (arch, kind, paged, int8)
                mirror.update(got)
            assert mirror == SH.cache_slot_leaves(cfg, paged=paged,
                                                  int8=int8)


def _kind_leaves(kind, paged, int8):
    if kind == "attn" and paged:
        return {"k_pages", "v_pages"} | (
            {"k_scale_pages", "v_scale_pages"} if int8 else set())
    if kind in ("attn", "local"):
        return {"k", "v"} | ({"k_scale", "v_scale"} if int8 else set())
    if kind == "ssm":
        return {"conv", "state"}
    if kind == "rglru":
        return {"conv", "h"}
    return set()


def test_mirror_spec_matches_auto_spec():
    from repro.dist.sharding import _auto_spec

    sizes = {"data": 2, "model": 2}
    for arch in sorted(all_configs()):
        cfg = get_config(arch, smoke=True)
        for name, shape in SH.param_leaves(cfg):
            got = SH.mirror_spec(name, shape, sizes)
            want = tuple(_auto_spec(name, shape, sizes))
            assert got == want, (arch, name, shape)


def test_expected_requests_match_decode_requests_per_width():
    """The coverage pass's independent runtime-shape derivation agrees
    with `engine.decode_requests` (the thing `plan_arch` consumes) on
    every surface of the reference posture: same request set at decode
    width 1 plus each admit width, per surface (and each admit width at
    batch 1 on the contiguous layout)."""
    from repro.engine.context import backend_in_bytes, decode_requests

    for cfg in all_configs().values():
        if not PC.servable(cfg):
            continue
        for surface in PC.surfaces(cfg):
            widths = (1,) + PC.admit_widths()
            if surface.speculate_k:
                widths += (surface.speculate_k + 1,)
            backend = PC.backend_for(surface)
            slot_pages = -(-PC.MAX_SEQ // PC.PAGE_SIZE)
            want = set()
            shapes = [(PC.BATCH, w) for w in sorted(set(widths))]
            if surface.layout == "contiguous":
                shapes += [(1, w) for w in PC.admit_widths()]
            for b, width in shapes:
                for req in decode_requests(
                        cfg, batch=b, seq=width,
                        dtype_bytes=backend_in_bytes(backend, 2),
                        out_bytes=2,  # plan_arch keeps the compute width
                        quantized_weights=surface.quantize,
                        sparse_weights=surface.sparse, density=0.5,
                        paged_pages=(slot_pages if surface.layout == "paged"
                                     else 0),
                        page_size=(PC.PAGE_SIZE if surface.layout == "paged"
                                   else 0)):
                    want.add(req.key())
            got = {req.key() for req, _ in PC.expected_requests(cfg, surface)}
            assert got == want, (cfg.name, surface.label(),
                                 sorted(got ^ want)[:4])
