"""Own device time of the block-sparse values' per-call relayout into
kernel tiles (``core/partitioner.pack_values``, under
``jax.named_scope("pack_values")``) per decode step: over the
``jit_decode_fn`` programs that ran whole in the traced window, over
their executions."""
import trace_scopes


def read(rec):
    red = trace_scopes.of_run(rec)
    return None if red is None else trace_scopes.per_run_ms(
        red, "pack_values")
