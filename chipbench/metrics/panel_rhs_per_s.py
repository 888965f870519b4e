"""Right-hand-side columns solved per second of the window."""


def read(run):
    if not run["solves"]:
        return None
    return run["rhs_columns"] * run["solves"] / run["window_s"]
