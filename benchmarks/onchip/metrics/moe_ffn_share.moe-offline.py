"""Expert FFN: device seconds of the expert layer's named instructions
over the device's busy seconds in the window, in %.

The named instructions are those ``lax.ragged_dot`` lowers to on a TPU
v5e: the grouped products over the held experts (``%ragged-dot-<mode>``,
Mosaic calls) and their group metadata (``%ragged-dot-metadata``), each
with a ``.<n>`` suffix where the program holds several.  The router, the
top-k, the compaction of the routed units and the weighted combine fuse
into unnamed XLA fusions and are not counted: this is the products' share,
a floor of the whole layer's (``benchmarks/moe_layer_chip.py`` reads the
whole layer from the two programs timed with and without it).  The instruction's own
name is matched, not its operands.  A trace without them, from a program
with no expert layer, gives None."""
import re

RAGGED_DOT = re.compile(r"%?ragged-dot-[a-z]+(\.\d+)?")


def expert_seconds(trace) -> float:
    return sum(s for op, s in trace.op_seconds.items()
               if RAGGED_DOT.fullmatch(op.partition(" = ")[0].strip()))


def read(view):
    if view.trace is None:
        return None
    secs = expert_seconds(view.trace)
    if not secs:
        return None
    return 100.0 * secs / view.trace.busy_s
