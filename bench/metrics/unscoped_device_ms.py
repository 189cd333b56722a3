"""Device time no program scope claims in one execution of the
serve-step program: ops with no ``op_name`` (copies XLA inserted) or one
that names no scope (the layer scan's own slicing and write-back), mean
over executions and devices."""
import progtrace


def read(ctx):
    pt = progtrace.of_run(ctx)
    if pt is None or not pt.scopes:
        return None
    return progtrace.scope_ms(pt, ctx.trace).get(progtrace.UNSCOPED, 0.0)
