"""The forward program (``act_batch``'s jitted P-head logits) in the solo
cell: the roofline time of the window's calls over the program's device
time in the profiler trace."""
import readers


def read(run):
    return readers.forward_roofline(run)
