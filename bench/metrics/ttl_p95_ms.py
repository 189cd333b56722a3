"""95th percentile of the gap between tokens (samples as ttl_p50_ms)."""
import readings


def read(ctx):
    v = readings.pct(readings.ttl_samples(ctx), 95)
    return None if v is None else v * 1e3
