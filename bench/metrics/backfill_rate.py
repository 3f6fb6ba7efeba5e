"""Station-hours of waveform fully processed in the window (its blocks'
pairs in the host filter) per second of the window's wall time."""


def read(ctx):
    return ctx["station_s"] / 3600.0 / ctx["window_s"]
