"""The sample-exact duplicate guard per push, in ms: the program's
``dedup`` span (``_flag_duplicates`` over every station), a part of
``ingest``. None where the program has no such span."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if "dedup" not in sp or n == 0:
        return None
    return sp["dedup"][1] / n * 1e3
