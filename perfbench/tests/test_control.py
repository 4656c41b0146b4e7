"""The precision control comes out as not correct, and the program as
correct, at a size the CPU holds: with `control`, the reference's first
choices in float8 e4m3 take the served tokens' place in the comparison
that decides `correct`, and read a widest logit gap far above the bf16
program's.

At this size (d_model 256, 2 blocks, vocabulary 16384, ~130 served
tokens) sound runs read 0.01-0.06 and the fp8 control 0.2-0.8, so the
test-size limit is 0.15.  The cells' own limits come from chip runs at
their published widths (PERF.md)."""

import pytest

import tiny

LIMIT = 0.15
SIZES = {
    "attention": dict(hidden_size=256, intermediate_size=512, head_dim=64,
                      vocab_size=16384),
    "mamba2": dict(d_model=256, headdim=32, d_state=32, vocab_size=16384),
}
TRAFFIC = {"output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                      "min": 16, "max": 32}}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("family", ["attention", "mamba2"])
def test_fp8_control_fails_where_the_program_passes(family, seed,
                                                    monkeypatch):
    monkeypatch.setitem(tiny.CONFS, family,
                        dict(tiny.CONFS[family], **SIZES[family]))
    sound = tiny.run(family, seed=seed, limit=LIMIT, traffic=TRAFFIC)
    assert sound["correct"], sound["checks"]
    control = tiny.run(family, seed=seed, control=True, limit=LIMIT,
                       traffic=TRAFFIC)
    assert not control["correct"], control["checks"]
    assert control["checks"]["bad_requests"]["value"] == 0
