"""Process start to the first timed round: imports, data and weights,
compilation, autotuning and the warm-up rounds."""


def read(rec):
    return rec["setup_s"]
