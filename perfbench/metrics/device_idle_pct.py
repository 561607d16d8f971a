"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of device op intervals) / window, from the device
profile of the run's first experiment."""


def read(run):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
