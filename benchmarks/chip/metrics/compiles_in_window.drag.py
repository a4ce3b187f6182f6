"""JAX traces inside the drag window: ``jax.compile`` spans of the
``jaxpr_trace_duration`` event, each a jit cache miss, whether or not
the persistent cache then spares the backend compile."""

import program_spans

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def read(rec):
    spans = program_spans.window(rec, "drag")
    if spans is None:
        return None
    return sum(sp["name"] == "jax.compile"
               and sp["attrs"].get("event") == TRACE_EVENT for sp in spans)
