"""sdpa_roofline.decode: least time the decode calls' attention core needs
(the ``avec.sdpa`` scope: the cache's K/V write and read, the score and
value products) over the device time of the decode program's operations
under that scope, from the profiler trace."""
from bench.metrics import _spans


def read(ctx):
    return _spans.scope_roofline_pct(ctx, "decode", "avec.sdpa")
