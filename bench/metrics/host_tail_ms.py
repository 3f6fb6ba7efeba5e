"""Host tail per push, in ms: the program's ``host_tail`` span (pair
consumption and the rolling filter of every station) plus the
benchmark's span around ``poll_detections``."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if n == 0:
        return None
    return (sp.get("host_tail", (0, 0.0))[1] + sp["poll"][1]) / n * 1e3
