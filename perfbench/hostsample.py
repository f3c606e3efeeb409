"""What the host's main thread is doing, sampled, for the traced run.

A daemon thread reads the main thread's Python stack every ``interval_s``
and keeps ``(wall-clock ns, label)``.  The label is the innermost frame of
the program under test (``<module>.<function>``, from a file under a
``repro`` package directory), else the innermost frame of the benchmark,
else ``"other"``.  The trace reduction names each idle stretch of the
device by the label seen most often inside it.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time

_PROGRAM = os.sep + "repro" + os.sep
_BENCH = os.sep + "perfbench" + os.sep


def _label(frame) -> str:
    bench = None
    while frame is not None:
        path = frame.f_code.co_filename
        if _PROGRAM in path:
            stem = os.path.splitext(os.path.basename(path))[0]
            return f"{stem}.{frame.f_code.co_name}"
        if bench is None and _BENCH in path:
            bench = "perfbench." + frame.f_code.co_name
        frame = frame.f_back
    return bench or "other"


class Sampler:
    def __init__(self, interval_s: float):
        self.interval_s = interval_s
        self.samples: list[tuple[int, str]] = []
        self._main = threading.main_thread().ident
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="perfbench-sampler")

    def _run(self):
        while not self._stop.wait(self.interval_s):
            frame = sys._current_frames().get(self._main)
            self.samples.append((time.time_ns(), _label(frame)))

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)

    def label_between(self, lo_ns: int, hi_ns: int) -> str:
        """The label seen most often between two wall-clock times; the
        nearest sample's where none falls inside."""
        if not self.samples:
            return "unsampled"
        inside = collections.Counter(
            lab for t, lab in self.samples if lo_ns <= t <= hi_ns)
        if inside:
            return inside.most_common(1)[0][0]
        mid = (lo_ns + hi_ns) / 2
        return min(self.samples, key=lambda s: abs(s[0] - mid))[1]
