"""Process start to the first timed step: imports, compile (or the compile
cache), weights and pipelines, and the three checked steps of each job."""


def read(run):
    return run["setup_s"]
