"""Device: 1 - (union of device-operation intervals) / window, from the
profiler trace, in %."""


def read(view):
    return None if view.trace is None else 100.0 * view.trace.idle_share
