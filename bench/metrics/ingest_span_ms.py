"""Ingest layer per push, in ms, read inside the program: its ``ingest``
spans (ring framing, the duplicate guard and the staging of the step's
inputs), children of the ``chunk`` span of each push. None where the
program has no ``chunk`` span: there ``ingest`` enclosed the step."""


def read(ctx):
    sp = ctx["spans"]
    n = sp["push"][0]
    if "chunk" not in sp or "ingest" not in sp or n == 0:
        return None
    return sp["ingest"][1] / n * 1e3
