"""flash_decode's share of its roofline: the least time of the decode
attention calls the traced steps made (one per layer; K and V of each
row's live positions, q and out, 4·len·q_heads·hsz operations; the
chips' share of each) over the kernel's device time."""
import readings

KERNEL = r"^flash_decode$"


def read(ctx):
    layers = ctx.cell.config["num_hidden_layers"]
    calls = [ctx.costs.flash_decode(ctx.cell.config, s.decode_lengths)
             for s in readings.traced_steps(ctx) if s.decode_lengths
             for _ in range(layers)]
    return readings.roofline(ctx, KERNEL, calls)
