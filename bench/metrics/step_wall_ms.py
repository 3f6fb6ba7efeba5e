"""Device step seen from the host, in ms: the program's ``fused_step``
span per execution (dispatch of the pool step and the one
``device_get`` that ends it)."""


def read(ctx):
    n, total = ctx["spans"].get("fused_step", (0, 0.0))
    return total / n * 1e3 if n else None
