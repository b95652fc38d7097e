"""1 - the union of device-op intervals over the traced window, from the
profiler's trace, averaged over the chips in use."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
