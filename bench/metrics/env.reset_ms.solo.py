"""Scalar environment: milliseconds per tenant start (fork the cluster
at the tenant's instant, warm its history up, start its predecessor),
from the program's ``env.reset`` spans. None where the program has no
such spans."""
import spans


def read(run):
    return spans.ms_per(run, ["env.reset"], "env.reset")
