"""The check that decides `correct`, driven through the rest of a run
with the timed path broken underneath (the chip look is skipped: the
tiny cell runs on the CPU).  Sound runs read gaps of at most 0.03 at
this size and broken ones 3 or more, so the test-size limit is 0.5."""

import pytest

import tiny

LIMIT = 0.5


@pytest.mark.parametrize("family", ["attention", "mamba2"])
def test_sound_run_is_correct(family):
    r = tiny.run(family, limit=LIMIT)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 10 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token", "state", "half"])
@pytest.mark.parametrize("family", ["attention", "mamba2"])
def test_broken_path_is_not_correct(family, fault):
    r = tiny.run(family, fault=fault, limit=LIMIT)
    assert not r["correct"], r["checks"]
