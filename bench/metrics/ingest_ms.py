"""Ingest layer per push, in ms: the benchmark's span around ``push``
less the program's ``fused_step`` and ``host_tail`` spans and the
benchmark's span around ``poll_detections`` (what is left is the ring
framing, the duplicate-window hashing and the staging of the block)."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if n == 0:
        return None
    rest = (sp["push"][1] - sp.get("fused_step", (0, 0.0))[1]
            - sp.get("host_tail", (0, 0.0))[1] - sp["poll"][1])
    return rest / n * 1e3
