"""Scheduler: the window's length over the ticks run in it, in ms."""


def read(view):
    n = len(view.log.ticks)
    return 1e3 * view.log.window_s / n if n else None
