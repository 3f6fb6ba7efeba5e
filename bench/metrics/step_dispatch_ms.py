"""The jitted pool step call until it returns, in ms per execution of
the step: the program's ``dispatch`` span over its ``fused_step`` count.
None where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp.get("fused_step", (0, 0.0))[0]
    if "dispatch" not in sp or n == 0:
        return None
    return sp["dispatch"][1] / n * 1e3
