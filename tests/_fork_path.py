"""The fork path as the tests' reference for the background timeline.

A ``ForkPathCache`` is a ``ReplayCheckpointCache`` whose timeline is
valid nowhere: every episode start lies past its ``valid_until``, so
every reset served off it (scalar env, vector env, serving lane) forks,
as it does past a real timeline's reach.
"""
from repro.core.provisioner import ReplayCheckpointCache


class _ValidNowhere:
    """A background timeline that certifies no instant."""
    valid_until = float("-inf")


class ForkPathCache(ReplayCheckpointCache):
    def timeline(self, until=None):
        return _ValidNowhere
