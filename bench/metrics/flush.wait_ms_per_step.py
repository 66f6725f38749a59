"""Wall milliseconds the host waits for flushes' train chunks
(``fleet.flush.wait``, the loss sync) per agent-step trained in the
window."""

from harness.window import window_spans


def read(run):
    steps = run.counts["agent_steps"]
    spans = window_spans(run, "fleet.flush.wait")
    if not steps or not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / steps * 1e3
