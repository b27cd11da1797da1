"""1 - union of the device-op intervals over the traced interval."""


def read(run):
    if not run.get("trace"):
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
