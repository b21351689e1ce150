"""Latency and rate arithmetic over one run's record.

Every number is taken over the whole window: a tail is the tail of every
request, a rate is all the window's tokens over the window's length.
Percentiles interpolate linearly between order statistics
(``numpy.percentile``'s default).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(xs, q: float) -> Optional[float]:
    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, q)) if xs.size else None


def due_in_window(record) -> list:
    return [t for t in record.requests.values()
            if record.open <= t.due < record.close]


def ttft_s(record) -> List[float]:
    """Due time to the end of the step that returned the first token, for
    every request due in the window that got one."""
    return [t.times[0] - t.due for t in due_in_window(record) if t.times]


def itl_s(record) -> List[float]:
    """Gaps between consecutive tokens of one request, for every gap that
    ends in the window."""
    out = []
    for t in record.requests.values():
        ts = t.times
        for a, b in zip(ts, ts[1:]):
            if record.open <= b < record.close:
                out.append(b - a)
    return out


def window_tokens(record) -> int:
    return sum(1 for t in record.requests.values() for x in t.times
               if record.open <= x < record.close)


def queue_wait_s(record) -> List[float]:
    """Due time to the start of the step that admitted the request."""
    return [t.admitted - t.due for t in due_in_window(record)
            if t.admitted is not None]


def attempted_failed(record) -> tuple:
    """Requests the window owes an answer, and those that failed.

    Open loop: every request due in the window; it fails without a first
    token or with a finish other than ``length``.  Closed loop: every
    request live at some time in the window; it fails with a finish other
    than ``length``."""
    if record.closed_loop:
        owed = [t for t in record.requests.values()
                if t.due < record.close
                and not (t.reason is not None and t.times
                         and t.times[-1] < record.open)]
        bad = [t for t in owed if t.reason not in (None, "length")]
    else:
        owed = due_in_window(record)
        bad = [t for t in owed
               if not t.times or t.reason not in (None, "length")]
    return len(owed), len(bad)
