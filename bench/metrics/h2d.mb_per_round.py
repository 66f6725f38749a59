"""Host-to-device megabytes per agent-round: the crop and location
batches collection stages for each dispatch (``fleet.collect``) plus the
ERB rows flushes upload on device-cache misses (``fleet.flush.pool``),
from the spans' ``h2d_bytes``."""

from harness.window import window_spans


def read(run):
    rounds = run.counts["agent_rounds"]
    spans = window_spans(run, "fleet.collect") + window_spans(run, "fleet.flush.pool")
    if not rounds or not spans:
        return None
    return sum(s["args"]["h2d_bytes"] for s in spans) / rounds / 1e6
