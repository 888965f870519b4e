"""Device milliseconds in collective ops per solve, from the traced window:
the device time (mean over chips) of the breakdown's ops that are
collectives, over the window's solves. It counts the per-level exchanges and
the final gather's psum of the solution.

The ops are read by name from the breakdown and not from the trace's
``collective_s``: on the TPU, XLA names the all-reduce of a ``psum`` after
the primitive (``psum.N``), which that sum's pattern does not match. The
breakdown keeps the ten ops with the most device time; in the four-chip
cell the exchange's psum holds about ten times the tenth op's time."""
import re

COLLECTIVE = re.compile(
    r"psum|ppermute|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all", re.I)


def read(run):
    t = run["trace"]
    if not t or not t["devices"] or not run["solves"]:
        return None
    s = sum(v for name, v in t["device_ops"] if COLLECTIVE.search(name))
    return s * 1e3 / run["solves"]
