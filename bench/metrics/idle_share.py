"""Device: share of the traced window in which no operation ran."""
UNIT = "%"


def read(run):
    if run.summary is None:
        return None
    return 100.0 * (1.0 - run.summary.busy_s / run.summary.window_s)
