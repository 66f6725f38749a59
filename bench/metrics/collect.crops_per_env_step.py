"""Observation rows cropped per environment step in collection: the
``crop_rows`` of the window's ``fleet.collect`` spans over their
``env_steps``. About 2 where each step crops its observation and its
next observation apart, about 1 where each location is cropped once. A
program whose spans carry no ``crop_rows`` reads nothing."""

from harness.window import window_spans


def read(run):
    spans = window_spans(run, "fleet.collect")
    if not spans or any("crop_rows" not in s["args"] for s in spans):
        return None
    steps = sum(s["args"]["env_steps"] for s in spans)
    if not steps:
        return None
    return sum(s["args"]["crop_rows"] for s in spans) / steps
