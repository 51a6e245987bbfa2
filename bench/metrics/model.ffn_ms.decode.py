"""Own device time of the feed-forward part (``jax.named_scope("ffn")``:
its norm, its projections and, on a block-sparse configuration, the
per-call relayout) per decode step: over the ``jit_decode_fn`` programs
that ran whole in the traced window, over their executions."""
import trace_scopes


def read(rec):
    red = trace_scopes.of_run(rec)
    return None if red is None else trace_scopes.per_run_ms(red, "ffn")
