"""Service layer, solo mode: milliseconds per decision in the lanes'
``apply`` (journal, step the tenant's own simulator, push its history,
refresh its observation), from the program's ``lane.apply`` spans."""
import spans


def read(run):
    return spans.ms_per(run, ["lane.apply"], "lane.apply")
