"""idle_in_call_pct: share of the traced window in which the chip was idle
while the destination held a call (device_idle_pct less this share is the
chip waiting on the wire and the edge client).

Read from host marks less trace busy time: the union of the calls'
residences (``dest.in`` .. ``dest.out`` marks, ``time.time_ns()``) less the
chip's busy time in the profiler trace (``busy_s``).  That holds while every
device operation of the window runs inside a call, as the destination puts
the arguments, dispatches and waits for the chip between the two marks and
runs nothing else.  A trace busier than the residences breaks that, and
reads nothing.  ``bench/timeline_cell.py`` reads the same share from the
destination's ``avec.call:*`` profiler annotations."""
from bench.metrics import _timeline


def read(ctx):
    tr, window = ctx["trace"], ctx["window_s"]
    if tr is None or window <= 0:
        return None
    held = _timeline.held_s(ctx)
    if held is None or tr["busy_s"] <= 0 or tr["busy_s"] > held:
        return None
    return 100.0 * (held - tr["busy_s"]) / window
