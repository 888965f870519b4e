"""Device dispatches per solve from ``dispatch_stats``: ``fused_launches``
under a fused backend, else ``switch_dispatches``, averaged over the mix's ops."""


def read(run):
    return run["counters"]["launches_per_solve"]
