"""Host milliseconds per solve in the executor's launch phase (concatenate to
the return of the compiled call), from the program's ``executor.launch_us``;
the calls that compile are not in it."""
from chipbench.counters import program


def read(run):
    h = program(run).get("executor.launch_us")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] / 1e3
