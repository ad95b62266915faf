"""Peak device memory in use over the run (the allocator's
``peak_bytes_in_use``, read after the window), in GB of 1e9 bytes."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
