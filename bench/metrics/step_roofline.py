"""The pool step's share of its roofline, in %: the least time the chip
needs for the step's algorithmic work (``bench/work.py``: the larger of
FLOPs over peak FLOP/s and bytes over HBM bandwidth) over the step's
device time from the trace."""
from bench import work


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["step_device_s"]:
        return None
    t_min, _ = work.least_time(ctx["work"], work.peaks(ctx["device_kind"]))
    return t_min / tr["step_device_s"] * 100.0
