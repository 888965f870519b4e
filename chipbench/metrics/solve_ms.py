"""Window milliseconds per single-RHS solve; forward and transpose each count one."""


def read(run):
    if run["rhs_columns"] != 1 or not run["solves"]:
        return None
    return run["window_s"] * 1e3 / run["solves"]
