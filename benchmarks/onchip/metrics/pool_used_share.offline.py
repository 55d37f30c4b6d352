"""Cache manager: mean over the window's ticks of pages in use over pages
in the pool (``PagedCache.pages_in_use()`` / ``num_pages``), in %."""


def read(view):
    used = [t.pages_in_use for t in view.log.ticks
            if t.pages_in_use is not None]
    if not used:
        return None
    return 100.0 * sum(used) / len(used) / view.log.num_pages
