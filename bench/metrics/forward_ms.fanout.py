"""Whole forward pass, as the host sees it: mean time per served call
inside the program's ``serve.forward`` span, from the dispatch of the
forward until the token is on the host (so it holds the wait for the
weights' copies too)."""
from bench import program_spans


def read(run):
    return program_spans.served_mean_ms(run, "serve.forward")
