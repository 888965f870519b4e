"""Seconds of analysis and plan building (``ctx.analyse`` and ``ctx.plan``
for each sweep direction the mix uses), by the harness's timer."""


def read(run):
    return run["timers"]["plan_s"]
