"""The whole solo serve step's share of the chip's peak: model operations
of the decisions answered over the window's seconds times the peak."""
import readers


def read(run):
    return readers.forward_mfu(run)
