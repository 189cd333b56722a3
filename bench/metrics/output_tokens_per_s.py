"""Output tokens the host received in the window, over its seconds."""


def read(ctx):
    n = sum(k for _, _, k, _ in ctx.deliveries)
    return n / (ctx.t_close - ctx.t_open) if n else None
