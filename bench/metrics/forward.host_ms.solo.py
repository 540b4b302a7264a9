"""Policy forward, host side, in the solo cell: milliseconds per
``act_batch`` call spent launching the forward (the observations' copy in,
the dispatch and the softmax) and turning its outputs into actions, from
the program's ``forward.launch`` and ``forward.fetch`` spans. None where the
program has no spans."""
import spans


def read(run):
    return spans.ms_per(run, ["forward.launch", "forward.fetch"],
                        "forward.wait")
