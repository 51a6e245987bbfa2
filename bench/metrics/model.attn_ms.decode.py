"""Own device time of the attention mixer (``jax.named_scope("attn")``:
the q/k/v/o projections, rope, the KV-cache write and the attention over
the cache) per decode step: over the ``jit_decode_fn`` programs that ran
whole in the traced window, over their executions."""
import trace_scopes


def read(rec):
    red = trace_scopes.of_run(rec)
    return None if red is None else trace_scopes.per_run_ms(red, "attn")
