"""Share of the window that the loop spent blocked in the loader's next()."""


def read(run):
    return 100.0 * run["loader"].wait_s() / run["window_s"]
