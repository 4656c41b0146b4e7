#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload qwen2-1.5b.chat --seed 7 \
        --seconds 40 --trace 0

Run from the root of a checkout.  The cell, its configuration and its
traffic mix are looked up by name in `BENCHMARK.json`; everything that
belongs to one of them lives in a file of its own:

    perfbench/configs/<config>.json   sizes, as run
    perfbench/configs/<config>.py     weights from the seed, the plain
                                      float32 reference, the mapping to
                                      the program's config
    perfbench/traffic/<traffic>.json  the schedule of work and posture
    perfbench/runners/<runner>.py     what the traffic file's "runner"
                                      names: drives the program
    perfbench/cells/<workload>.json   the limits `correct` is held to
    perfbench/metrics/<metric>.py     one reader per metric

With `--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics (the window is then profiled).  The
last line of standard output is one JSON object; the numbers that decide
`correct` are printed beside their limits as the last lines of standard
error and under the result's last key, `checks`.

The run fails, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for, and when the program is not in the
checkout.  `--control 1` is for setting limits, never a benchmark run:
the reference in float8 e4m3 takes the program's place in the
comparison, on the same prompts and tokens, and has to come out as not
correct; the served tokens' own gap is printed beside it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(HERE / ".out" / "tpu_logs"))
ROOT = Path.cwd()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.find_cell(bench, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("perfbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    harness.require_chips(cell["chips"])
    ctx = harness.context(
        bench, cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), control=bool(args.control),
        t_process=T_PROCESS, out_dir=HERE / ".out")
    result = harness.run_cell(bench, ctx)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
