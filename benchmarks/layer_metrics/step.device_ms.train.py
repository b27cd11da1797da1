"""Device busy time in the traced interval over the optimizer steps in it."""


def read(run):
    if not run.get("trace"):
        return None
    steps = run["trace"]["step_runs"]
    return 1e3 * run["trace"]["busy_s"] / steps if steps else None
