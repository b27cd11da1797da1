"""The fullest device's ``peak_bytes_in_use`` after the window, the figure
the driver's memory floor reads."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
