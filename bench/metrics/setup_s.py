"""Set-up seconds: process start to the first timed push (loading,
frozen statistics, building the detector, warm-up and compilation)."""


def read(ctx):
    return ctx["setup_s"]
