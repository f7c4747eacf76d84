"""dest_host_ms.decode: median over decode calls of the destination's
residence (dest.out - dest.in) less its execute.device wait for the chip."""
from bench.metrics import _timeline


def read(ctx):
    return _timeline.dest_host_ms(ctx, "decode")
