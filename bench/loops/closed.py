"""Closed loop, one client served in the calling thread: the next request
is sent when the previous one has returned."""
from __future__ import annotations

import time
import traceback


def drive(call, order, *, seconds=None, count=None):
    """Send requests from ``order`` (an iterator of request keys) until
    ``seconds`` have passed since the first was sent, or ``count`` have
    been sent.  Returns one record per request sent:
    (key, sent, done, answer, counters, error)."""
    records = []
    t_start = time.perf_counter()
    while True:
        if count is not None and len(records) >= count:
            break
        if seconds is not None and time.perf_counter() - t_start >= seconds:
            break
        key = next(order)
        sent = time.perf_counter()
        try:
            answer, counters = call(key)
            error = None
        except Exception:                  # counted as failed, never a pass
            answer, counters = None, None
            error = traceback.format_exc(limit=-3)
        records.append((key, sent, time.perf_counter(), answer, counters,
                        error))
    return records
