"""The channel merge per push, in ms: the program's ``merge`` spans (the
sum of each (dt, idx1) similarity over a station's components and its
threshold, inside ``filter_close``), summed over the window, ÷ pushes.
None where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if "merge" not in sp or n == 0:
        return None
    return sp["merge"][1] / n * 1e3
