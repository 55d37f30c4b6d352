"""Model step: the least bytes the window's work needs
(``workcount.least_bytes``: per tick the weights once at the compute
width, each sequence's live KV read once and the KV it writes), over the
window's length times the chip's peak HBM bytes/s, in %."""
import workcount


def read(view):
    if not view.log.ticks:
        return None
    b = workcount.least_bytes(view.config, view.log.ticks)
    return 100.0 * b / (view.log.window_s * view.peaks["hbm_bytes_per_s"])
