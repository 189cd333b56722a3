"""Device time of the paged pool's plumbing in one execution of the
serve-step program: ops in the ``kv_pool`` scope (a layer's pool planes
selected, reshaped, appended to or written back) and copies of the
step's pool arguments, mean over executions and devices."""
import progtrace


def read(ctx):
    pt = progtrace.of_run(ctx)
    if pt is None or not pt.scopes:
        return None
    return progtrace.scope_ms(pt, ctx.trace).get("kv_pool", 0.0)
