"""Host time of one engine step: each ``serve.step`` span of the traced
window less the union of its ``serve.readback`` spans (the blocking
device-to-host transfers), mean over steps."""
import progtrace


def read(ctx):
    pt = progtrace.of_run(ctx)
    return None if pt is None else progtrace.host_step_ms(
        pt, *ctx.trace.window())
