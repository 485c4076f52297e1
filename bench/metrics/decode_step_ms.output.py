"""Model step: ``decode_step_ms`` in the cells where it moves the output
rate rather than the inter-token tail."""
from bench import harness


def value(run):
    return harness.metric_reader("decode_step_ms")(run)
