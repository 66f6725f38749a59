"""Wall seconds of collection per agent-round: the ``fleet.collect``
spans (crops, the q-value dispatch and its sync, epsilon-greedy, ERB
appends) over the rounds run."""

from harness.window import window_spans


def read(run):
    rounds = run.counts["agent_rounds"]
    spans = window_spans(run, "fleet.collect")
    if not rounds or not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / rounds
