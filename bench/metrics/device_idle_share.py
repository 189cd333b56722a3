"""Share (%) of the traced window in which no op ran on a device, mean
over the cell's devices."""
import readings


def read(ctx):
    busy = readings.device_busy_share(ctx)
    return None if busy is None else 100.0 * (1.0 - busy)
