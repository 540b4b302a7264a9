"""Scalar environment: milliseconds per decision spent stepping the
tenants' own simulators, from the program's ``env.advance`` spans over its
``lane.apply`` count. None where the program has no such spans."""
import spans


def read(run):
    return spans.ms_per(run, ["env.advance"], "lane.apply")
