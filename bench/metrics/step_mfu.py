"""Whole decode steps' share of the chips' peak: the least time of the
model work the window completed (every decode step's weight and K/V
bytes and operations, at the chips' peaks) over the window's seconds."""
import readings


def read(ctx):
    if ctx.peak is None:
        return None
    c = ctx.cell.config
    need = sum(readings.least_wall(ctx, ctx.costs.decode_step(c, s.decode_lengths))
               for s in ctx.steps if s.decode_lengths)
    return 100.0 * need / (ctx.t_close - ctx.t_open) if need else None
