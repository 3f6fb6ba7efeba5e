"""Device time of the pool step, in ms per execution: the union of the
operations of the step program in the profiler trace, on the busiest
chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["step_device_s"] is None:
        return None
    return tr["step_device_s"] * 1e3
