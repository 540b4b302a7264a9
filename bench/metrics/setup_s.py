"""Set-up: process start to window open (loading, building the world and
the agent, compiling, warming up)."""


def read(run):
    return run.setup_s
