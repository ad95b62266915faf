"""JAX compile inside calls: the program's ``jax.compile`` spans (one per
backend compile, on the compiling thread) that carry a served call's id,
summed and divided by the number of served calls.  Reads 0 for a program
that records ``serve.forward`` and compiles nothing inside calls; silent
for one that records neither."""
from bench import program_spans


def read(run):
    return program_spans.served_mean_ms(run, "jax.compile",
                                        witness="serve.forward")
