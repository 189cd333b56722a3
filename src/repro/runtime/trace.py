"""Names the serving engine writes into a ``jax.profiler`` trace.

Host spans: ``DecodeEngine.step`` opens ``serve.step`` (a
``StepTraceAnnotation``, so XProf's step view finds the steps) and one
span per phase inside it (``SPANS``).  Device scopes: the step programs
put their ops under ``jax.named_scope`` names (``SCOPES``), which reach
every compiled op's ``op_name`` metadata.

A ``TraceAnnotation`` records only while a profiler session is active;
otherwise it costs its construction, about a microsecond.  There is no
switch.  The benchmark's trace reduction reads both tuples.
"""
from __future__ import annotations

import jax

SPANS = (
    "serve.step",        # one DecodeEngine.step; arg step_num
    "serve.admit",       # restores and admission
    "serve.prefill",     # one prefill chunk; args rid (oldest row), tokens
    "serve.decode",      # the decode phase: dispatch, readback, replay
    "serve.dispatch",    # host preparation and the step program's enqueue
    "serve.readback",    # a blocking device->host transfer
    "serve.replay",      # tokens into requests, retirements, pool sample
    "serve.govern",      # the TTL governor's decision
)

SCOPES = (
    "embed",             # token embedding (and positions)
    "attn",              # attention sublayer: norm, kernel, out-projection
    "qkv",               # q/k/v projections and rope
    "kv_pool",           # selecting, reshaping, appending to and writing
                         # back a layer's K/V pool planes
    "combine",           # the all-to-all and LSE combine (KVP > 1)
    "ffn",               # FFN sublayer: norm, matmuls, activation
    "lm_head",           # final norm, logits matmul, vocab mask
    "sample",            # the token decision
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``name`` (one of ``SPANS``) with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(step_num: int) -> jax.profiler.StepTraceAnnotation:
    """The ``serve.step`` span of engine step ``step_num``."""
    return jax.profiler.StepTraceAnnotation("serve.step", step_num=step_num)

