"""Scalar environment: milliseconds per decision spent encoding the
tenants' snapshots into their histories and refreshing their
observations, from the program's ``env.encode`` spans over its
``lane.apply`` count. None where the program has no such spans."""
import spans


def read(run):
    return spans.ms_per(run, ["env.encode"], "lane.apply")
