"""Device: 1 minus the union of device operation intervals over the
traced window, as a share."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
