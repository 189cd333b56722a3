"""Median gap between tokens: every token after a request's first that
the host received in the window is one sample, the time since that
request's previous delivery over the tokens delivered together."""
import readings


def read(ctx):
    v = readings.pct(readings.ttl_samples(ctx), 50)
    return None if v is None else v * 1e3
