"""Provisioning decisions the service applied in the window, over the
window's seconds: the episodes' starts count, and so do the in-flight
batch that drains after the end and its time."""


def read(run):
    return run.counts["decisions"] / run.window_s
