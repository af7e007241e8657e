"""Process start to the first timed operation: data generation, index
builds, warm-up and, where the cache misses, compilation (host clock)."""


def read(run):
    return run.setup_s
