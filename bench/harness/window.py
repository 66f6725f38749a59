"""The program's own spans of the timed window.

The fed traffic's set-up (``drivers/fed.py``, ``Session._warm_flushes``)
flushes through the traced engine and never collects, while in the
window every job collects before its first flush. So the window's spans
are those that start at or after the first ``fleet.collect`` span. A
program that records no ``fleet.collect`` span gives none.
"""

from __future__ import annotations


def window_spans(run, name: str) -> list[dict]:
    """``name``'s wall spans that started inside the timed window."""
    collects = run.spans("fleet.collect")
    if not collects:
        return []
    start = min(s["t0"] for s in collects)
    return [s for s in run.spans(name) if s["t0"] >= start]
