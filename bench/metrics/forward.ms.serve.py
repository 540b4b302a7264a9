"""Policy forward: host milliseconds per ``act_batch`` call, from the call
to the actions on the host."""


def read(run):
    return run.spans.mean_ms("forward")
