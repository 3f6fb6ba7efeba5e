"""Network association per push, in ms: the program's ``detections`` span
around ``poll_detections``. None where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if "detections" not in sp or n == 0:
        return None
    return sp["detections"][1] / n * 1e3
