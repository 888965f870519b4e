"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) in the run, from the program's ``jit.compile_s``. Set-up
warms every shape the window uses, so all of it is set-up's."""
from chipbench.counters import program


def read(run):
    return program(run).get("jit.compile_s") or None
