"""``unscoped_share`` (device): percent of device 0's operation time in the
traced window that no scope of the program or of a flax module names
(``harness/sections.py``): the check that the sections are whole."""
from harness import sections


def read(ctx):
    seconds = sections.read(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds["unscoped"] / sum(seconds.values())
