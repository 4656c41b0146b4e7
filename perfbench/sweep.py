#!/usr/bin/env python3
"""Find a cell's knee once: its traffic at several fixed rates in one
process (one set-up), each rate a window of its own.

    python3 perfbench/sweep.py --workload qwen2-1.5b.chat --seed 5 \
        --seconds 30 --rates 1.5,2,2.5,3,3.5,4

Prints one JSON line per rate: TTFT and inter-token tails, requests
offered and finished in the window, and the queue left when it closed.
The knee is the highest rate whose window keeps up; the cell's traffic
file then runs at about four fifths of it.  Not a benchmark run.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(HERE / ".out" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(Path.cwd() / "src"))
    import harness
    import readings
    import traffic

    bench = json.loads((Path.cwd() / "BENCHMARK.json").read_text())
    cell = harness.find_cell(bench, args.workload)
    harness.require_chips(cell["chips"])
    h = harness.context(bench, cell, seed=args.seed, seconds=args.seconds,
                        trace=False, control=False, t_process=T_PROCESS,
                        out_dir=HERE / ".out")
    run = harness.load_runner(h.traffic["runner"]).Runner(h)
    run.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        run.spec = dict(h.traffic, rate_rps=rate)
        run.items = traffic.schedule(
            run.spec, run.spec["lead_s"] + h.seconds
            + run.spec["drain_cap_s"])
        run.ticks, run.lateness = [], []
        run.run()
        window = readings.window_requests(run)
        queued_at_close = sum(r.admit != r.admit or r.admit >= run.we
                              for r in run.requests if r.due < run.we)
        print(json.dumps({
            "rate_rps": rate, "due": len(window),
            "finished": sum(r.done for r in window),
            "queued_at_close": queued_at_close,
            "ttft_p50_ms": readings.pct(readings.ttft_ms(run), 50),
            "ttft_p90_ms": readings.pct(readings.ttft_ms(run), 90),
            "itl_p99_ms": readings.pct(readings.itl_ms(run), 99),
            "queue_wait_p90_ms": readings.pct(readings.queue_wait_ms(run),
                                              90),
            "tokens_per_s": readings.tokens_in_window(run) / h.seconds}),
            flush=True)
        run.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
