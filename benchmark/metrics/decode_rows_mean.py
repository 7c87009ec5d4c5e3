"""Live rows per decode step: the ``active`` argument of the engine's
``serve.decode`` spans."""
from benchmark.harness import readers


def read(facts):
    return readers.span_arg_mean(facts, "serve.decode", "active")
