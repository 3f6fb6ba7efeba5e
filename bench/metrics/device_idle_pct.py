"""Share of the traced window in which no operation ran on the device,
in %, averaged over the chips used."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return tr["idle_share"] * 100.0
