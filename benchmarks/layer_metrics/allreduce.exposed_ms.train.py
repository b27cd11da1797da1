"""Per step, the part of the all-reduce events during which no compute runs
on that device (mean over the devices)."""


def read(run):
    if not run.get("trace"):
        return None
    steps = run["trace"]["step_runs"]
    if run["chips"] < 2 or not steps or not run["trace"]["collective_s"]:
        return None
    return 1e3 * run["trace"]["collective_exposed_s"] / steps
