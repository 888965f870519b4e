"""Share of the solve's roofline, in percent: least time per solve over the
device's busy time per solve."""
from chipbench.roofline import roofline_share


def read(run):
    return roofline_share(run)
