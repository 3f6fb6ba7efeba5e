"""Filter-window closes per push, in ms: the program's ``filter_close``
spans (one station's occurrence filter per component, channel merge and
clustering at a window close), summed over the window, ÷ pushes. None
where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if "filter_close" not in sp or n == 0:
        return None
    return sp["filter_close"][1] / n * 1e3
