"""Named accumulating timers and the at-exit phase report.

Reference: include/LightGBM/utils/common.h:980 (Common::Timer / global_timer, RAII
FunctionTimer, printed at exit under USE_TIMETAG).  The engine's phases are the
telemetry tracer's boundary spans (lightgbm_tpu.telemetry.tracer), accumulated
there and nowhere else; ``LIGHTGBM_TPU_TIMETAG=1`` prints them at exit, hot spots
first.  ``Timer`` stays for ad-hoc scopes of a caller's own.
"""
from __future__ import annotations

import atexit
import contextlib
import os
import time
from collections import defaultdict
from typing import Dict, Iterator, Mapping, Optional

from ..telemetry.tracer import global_tracer


def format_report(totals: Mapping[str, float],
                  counts: Mapping[str, int]) -> str:
    """Hot spots first: sorted by total time descending, with per-call
    mean (the alphabetical order of the original hid the hot paths)."""
    lines = []
    for name, total in sorted(totals.items(), key=lambda kv: -kv[1]):
        n = counts.get(name, 0)
        mean_ms = total / n * 1e3 if n else 0.0
        lines.append(f"{name}: {total:.3f}s ({n} calls, "
                     f"{mean_ms:.3f} ms/call)")
    return "\n".join(lines)


class Timer:
    """Accumulating named wall-clock timer (host-side).

    ``enabled`` re-reads ``LIGHTGBM_TPU_TIMETAG`` lazily on every check, so
    setting the env var after import works; :meth:`enable`/:meth:`disable`
    (or assigning ``enabled``) override the env var for this process."""

    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self._enabled_override: Optional[bool] = None

    @property
    def enabled(self) -> bool:
        if self._enabled_override is not None:
            return self._enabled_override
        return os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0")

    @enabled.setter
    def enabled(self, value: bool) -> None:
        self._enabled_override = bool(value)

    def enable(self) -> None:
        self._enabled_override = True

    def disable(self) -> None:
        self._enabled_override = False

    def reset_enabled(self) -> None:
        """Drop any override; follow the env var again."""
        self._enabled_override = None

    @contextlib.contextmanager
    def scope(self, name: str) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.totals[name] += time.perf_counter() - t0
            self.counts[name] += 1

    def report(self) -> str:
        return format_report(self.totals, self.counts)


# the LIGHTGBM_TPU_TIMETAG switch (read lazily) for the at-exit report
global_timer = Timer()


def phase_report() -> str:
    """The tracer's cumulative phases (every boundary span, plus ordinary
    spans recorded while telemetry was enabled) in the timer's format."""
    return format_report(global_tracer.phase_snapshot(),
                         global_tracer.phase_counts())


@atexit.register
def _print_timers() -> None:
    if global_timer.enabled:
        report = phase_report()
        if report:
            print("[LightGBM-TPU] timers:\n" + report)
