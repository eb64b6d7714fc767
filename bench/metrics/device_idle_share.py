"""Share of the traced window in which no op ran on the device: 1 - busy /
window, busy being the union of device-op intervals, mean over the chips."""


def read(run):
    if run.trace is None or not run.trace.chips or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
