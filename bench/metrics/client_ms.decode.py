"""client_ms.decode: median over decode calls of (client.return -
client.call) less (client.recv - client.sent): the edge client's own time."""
from bench.metrics import _timeline


def read(ctx):
    return _timeline.client_ms(ctx, "decode")
