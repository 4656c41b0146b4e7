"""The one traffic generator: a cell's whole schedule of work from its
traffic file, never from `--seed`.

A traffic file fixes, under its own `schedule_seed`, every request's
due time, prompt length and output length.  `--seed` draws only the
weights and the prompt token ids, so every seed does the same work.

Keys read here:

    arrivals        "open": requests are due on a schedule whatever the
                    server does; "closed": a backlog of `backlog`
                    requests waits at all times, each due when queued
    rate_rps        open loop: mean arrival rate
    modulation      optional {"period_s": P, "phases": [[share, mult],
                    ...]}: within each period the rate runs at
                    mult * rate_rps for share * P seconds, in order; the
                    shares sum to 1 and the mean multiplier is 1
    prompt, output  {"dist": "lognormal", "median": m, "sigma": s,
                    "min": lo, "max": hi}: lengths in tokens, clipped
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    idx: int
    due_s: float      # offset from the start of traffic; 0 when closed
    prompt_len: int
    max_new: int


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _rate_mult(mod: dict | None) -> tuple[np.ndarray, np.ndarray, float]:
    """Phase boundaries (seconds within a period) and multipliers."""
    if not mod:
        return np.array([0.0, 1.0]), np.array([1.0]), 1.0
    shares = np.array([p[0] for p in mod["phases"]], float)
    mults = np.array([p[1] for p in mod["phases"]], float)
    if abs(shares.sum() - 1) > 1e-9 or abs((shares * mults).sum() - 1) > 1e-9:
        raise ValueError("modulation shares must sum to 1 with mean "
                         "multiplier 1")
    period = float(mod["period_s"])
    return np.concatenate([[0.0], np.cumsum(shares)]) * period, mults, period


def _arrival_times(rate: float, mod: dict | None, unit_gaps: np.ndarray
                   ) -> np.ndarray:
    """Map unit-rate exponential arrival epochs through the inverse of
    the integrated rate, so a modulated rate bends the same draws."""
    bounds, mults, period = _rate_mult(mod)
    epochs = np.cumsum(unit_gaps) / rate  # in "mean-rate seconds"
    # integrated multiplier over one period equals `period`
    cum = np.concatenate([[0.0], np.cumsum(np.diff(bounds) * mults)])
    whole, frac = np.divmod(epochs, period)
    k = np.searchsorted(cum, frac, side="right") - 1
    k = np.clip(k, 0, len(mults) - 1)
    return whole * period + bounds[k] + (frac - cum[k]) / mults[k]


def schedule(spec: dict, horizon_s: float) -> list[Item]:
    """Every request of the mix due before `horizon_s` (open loop), or
    enough to cover it (closed loop: as many as could possibly be
    served, capped by `max_requests`)."""
    rng = np.random.default_rng(spec["schedule_seed"])
    # separate streams so a longer horizon extends the same schedule
    s_arr, s_in, s_out = rng.spawn(3)
    n = int(spec["max_requests"])
    prompts = _lengths(spec["prompt"], s_in, n)
    outputs = _lengths(spec["output"], s_out, n)
    if spec["arrivals"] == "closed":
        due = np.zeros(n)
    elif spec["arrivals"] == "open":
        due = _arrival_times(float(spec["rate_rps"]), spec.get("modulation"),
                             s_arr.exponential(1.0, n))
        if due[-1] < horizon_s:
            raise ValueError(f"max_requests {n} ends at {due[-1]:.1f} s, "
                             f"before the horizon {horizon_s:.1f} s")
        n = int(np.searchsorted(due, horizon_s))
    else:
        raise ValueError(f"unknown arrivals {spec['arrivals']!r}")
    return [Item(i, float(due[i]), int(prompts[i]), int(outputs[i]))
            for i in range(n)]


def prefill_widths(spec: dict) -> list[int]:
    """Every admit width the scheduler can use for this mix: the bucket
    multiples from the shortest prompt the distribution allows to the
    longest."""
    b = spec["serve"]["prefill_bucket"]
    lo = -(-spec["prompt"]["min"] // b) * b
    hi = -(-spec["prompt"]["max"] // b) * b
    return list(range(lo, min(hi, max_seq(spec)) + 1, b))


def max_seq(spec: dict) -> int:
    """The pool's rows per slot: longest prompt plus output, rounded up
    to the prefill bucket."""
    b = spec["serve"]["prefill_bucket"]
    need = spec["prompt"]["max"] + spec["output"]["max"]
    return -(-need // b) * b


def prompt_tokens(seed: int, idx: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request `idx`: drawn from `--seed` alone, the same
    for a request whatever the window length."""
    return np.random.default_rng([seed, idx]).integers(
        0, vocab, length, dtype=np.int32)
