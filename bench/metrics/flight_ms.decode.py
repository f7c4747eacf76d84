"""flight_ms.decode: median over decode calls of (client.recv - client.sent)
less the destination's residence (dest.out - dest.in)."""
from bench.metrics import _timeline


def read(ctx):
    return _timeline.flight_ms(ctx, "decode")
