"""Wall milliseconds building flushes' replay pools (``fleet.flush.pool``:
ERB flattening, uploads on device-cache misses, zero padding,
concatenation) per agent-step trained in the window."""

from harness.window import window_spans


def read(run):
    steps = run.counts["agent_steps"]
    spans = window_spans(run, "fleet.flush.pool")
    if not steps or not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / steps * 1e3
