"""A watch on the measured loop from a thread of its own.

Every `period_s` the watch wakes and reads the loop's heartbeat.  Where
the loop has not beaten for `limit_s`, it notes where the loop's thread
stands and, once the loop beats again, the process's CPU time, context
switches and page faults meanwhile.  It also keeps its own longest
lateness: a watch that wakes late together with the loop means no
thread of the process ran.  It writes to standard error only, after the
window; it costs one short wake-up per period.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback


def _proc() -> dict:
    t = os.times()
    out = {"cpu_user_s": t.user, "cpu_sys_s": t.system}
    try:
        for line in open("/proc/self/status"):
            k, _, v = line.partition(":")
            if k in ("voluntary_ctxt_switches", "nonvoluntary_ctxt_switches"):
                out[k] = int(v)
        f = open("/proc/self/stat").read().rsplit(")", 1)[1].split()
        out["minflt"], out["majflt"] = int(f[7]), int(f[9])
    except OSError:
        pass
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: round(b[k] - a[k], 3) for k in a
            if k in b and b[k] != a[k]}


class Watch:
    def __init__(self, limit_s: float = 0.75, period_s: float = 0.02):
        self.limit_s, self.period_s = limit_s, period_s
        self.beat = time.perf_counter()
        self.events: list[dict] = []
        self.late = (0.0, 0.0)          # (longest lateness, when)
        self._main = threading.get_ident()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="bench-watch",
                                        daemon=True)

    def __enter__(self):
        self.beat = time.perf_counter()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def _where(self) -> str:
        frame = sys._current_frames().get(self._main)
        if frame is None:
            return "?"
        return " < ".join(f"{os.path.basename(f.filename)}:{f.lineno} "
                          f"{f.name}" for f in
                          reversed(traceback.extract_stack(frame)[-4:]))

    def _loop(self) -> None:
        clock = time.perf_counter
        last = clock()
        ev = None
        while not self._stop.wait(self.period_s):
            now = clock()
            late = now - last - self.period_s
            if late > self.late[0]:
                self.late = (late, now)
            last = now
            beat = self.beat
            if ev is None and now - beat > self.limit_s:
                ev = {"beat": beat, "where": self._where(), "proc": _proc()}
            elif ev is not None and beat > ev["beat"]:
                ev["length_s"] = beat - ev["beat"]
                ev["proc"] = _delta(ev["proc"], _proc())
                self.events.append(ev)
                ev = None

    def report(self, t0: float) -> list[str]:
        """Lines for standard error, times from `t0`: the longest waits
        between beats, and the watch's own longest lateness."""
        lines = [f"watch: own longest lateness {self.late[0] * 1e3:.1f} ms "
                 f"at {self.late[1] - t0:.2f} s; {len(self.events)} waits "
                 f"over {self.limit_s * 1e3:.0f} ms"]
        for ev in sorted(self.events, key=lambda e: -e["length_s"])[:4]:
            lines.append(
                f"watch: {ev['length_s'] * 1e3:.1f} ms from "
                f"{ev['beat'] - t0:.2f} s in {ev['where']}; process "
                f"{ev['proc']}")
        return lines
