"""Proto-Faaslet weights onto the device (launch/serve.py infer): mean time
per served call inside the program's ``serve.weights`` span, the enqueue of
every leaf of the snapshot's weights onto the device."""
from bench import program_spans


def read(run):
    return program_spans.served_mean_ms(run, "serve.weights")
