"""Device idle share of the traced window, in percent (mean over chips)."""
from chipbench.roofline import idle_share


def read(run):
    return idle_share(run)
