"""Policy forward, the device's share seen from the host, in the solo
cell: milliseconds per ``act_batch`` call spent waiting for the
probabilities on the host, from the program's ``forward.wait`` spans. None
where the program has no spans."""
import spans


def read(run):
    return spans.ms_per(run, ["forward.wait"], "forward.wait")
