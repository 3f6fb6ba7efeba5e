"""The one ``device_get`` of the step's outputs after the device is done,
in ms per execution of the step: the program's ``pull`` span over its
``fused_step`` count. None where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp.get("fused_step", (0, 0.0))[0]
    if "pull" not in sp or n == 0:
        return None
    return sp["pull"][1] / n * 1e3
