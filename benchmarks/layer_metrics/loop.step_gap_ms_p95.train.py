"""95th percentile of the time between the loop's consecutive batch
requests, all steps of the window (from the harness's loader wrapper)."""


def read(run):
    gaps = sorted(run["loader"].gaps_ms())
    if len(gaps) < 20:
        return None
    return gaps[min(len(gaps) - 1, int(0.95 * len(gaps)))]
