"""Model step: model FLOPs of every prompt and output token the program
processed in the window (``workcount.model_flops``), over the window's
length times the chip's peak bf16 FLOP/s, in %."""
import workcount


def read(view):
    if not view.log.ticks:
        return None
    flops = workcount.model_flops(view.config, view.log.ticks)
    return 100.0 * flops / (view.log.window_s * view.peaks["bf16_flops"])
