"""The schedule of work is the cell's, never the seed's."""

import json

import numpy as np
import pytest

import tiny  # noqa: F401  (puts perfbench on the path)
import traffic

SPECS = {p.stem: json.loads(p.read_text())
         for p in (tiny.PERFBENCH / "traffic").glob("*.json")}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_schedule_is_fixed_and_extends(name):
    spec = SPECS[name]
    a = traffic.schedule(spec, 30.0)
    b = traffic.schedule(spec, 90.0)
    assert a == traffic.schedule(spec, 30.0)
    assert b[:len(a)] == a if spec["arrivals"] == "open" else a == b


@pytest.mark.parametrize("name", sorted(SPECS))
def test_seed_draws_token_ids_only(name):
    spec = SPECS[name]
    items = traffic.schedule(spec, 20.0)[:5]
    for it in items:
        x = traffic.prompt_tokens(7, it.idx, it.prompt_len, 1000)
        y = traffic.prompt_tokens(2**31 + 11, it.idx, it.prompt_len, 1000)
        assert x.shape == y.shape == (it.prompt_len,)
        assert not np.array_equal(x, y)
        assert np.array_equal(x, traffic.prompt_tokens(7, it.idx,
                                                       it.prompt_len, 1000))


def test_open_cells_have_a_tail_in_the_window():
    """ttft_p90 needs at least 100 requests due in the window."""
    bench = tiny.bench()
    for cell in bench["workloads"]:
        spec = SPECS[cell["traffic"]]
        if spec["arrivals"] != "open":
            continue
        lead = spec["lead_s"]
        items = traffic.schedule(spec, lead + bench["run_seconds"])
        due = [it for it in items if it.due_s >= lead]
        assert len(due) >= 100, (cell["name"], len(due))


def test_modulation_keeps_the_mean_and_bends_the_rate():
    spec = dict(SPECS["chat-burst"], max_requests=20000)
    items = traffic.schedule(spec, 4000.0)
    due = np.array([it.due_s for it in items])
    rate = len(due) / 4000.0
    assert rate == pytest.approx(spec["rate_rps"], rel=0.05)
    period = spec["modulation"]["period_s"]
    share, mult = spec["modulation"]["phases"][0]
    burst = (due % period) < share * period
    assert burst.mean() == pytest.approx(share * mult, rel=0.05)


def test_lengths_follow_their_clips():
    for spec in SPECS.values():
        items = traffic.schedule(spec, 60.0)
        p = [it.prompt_len for it in items]
        o = [it.max_new for it in items]
        assert spec["prompt"]["min"] <= min(p) and max(p) <= spec["prompt"]["max"]
        assert spec["output"]["min"] <= min(o) and max(o) <= spec["output"]["max"]
        assert max(p) + max(o) <= traffic.max_seq(spec)


def test_widths_cover_every_admit():
    spec = SPECS["chat"]
    assert traffic.prefill_widths(spec) == list(range(256, 2049, 256))
    assert traffic.max_seq(spec) == 2304
    spec = SPECS["code-backlog"]
    assert traffic.prefill_widths(spec) == list(range(1024, 3073, 256))


def test_every_cell_resolves_to_its_files():
    import harness
    bench = tiny.bench()
    for cell in bench["workloads"]:
        h = harness.context(bench, cell, seed=1, seconds=1.0, trace=False,
                            control=False, t_process=0.0, out_dir=tiny.HERE)
        assert h.traffic["runner"] == "serve"
        assert 0 < h.limits["max_logit_gap"]
        for m in harness.metric_names(bench, cell, False) + \
                harness.metric_names(bench, cell, True):
            assert (tiny.PERFBENCH / "metrics" / f"{m['name']}.py").is_file()


def test_serve_block_passes_through_to_the_program():
    runner = tiny.harness.load_runner("serve")
    scfg, kw = runner.serve_options(
        {"batch": 4, "prefill_bucket": 16, "cache_layout": "paged"}, 64)
    assert (scfg.batch, scfg.max_seq, scfg.cache_layout) == (4, 64, "paged")
    assert kw == {"prefill_bucket": 16}
    with pytest.raises(SystemExit, match="unknown serve keys"):
        runner.serve_options({"batch": 4, "slots": 8}, 64)
