"""Seconds to serve every batch size of every tenant once at set-up: the
first call of each bucket program and of each pad and slice."""


def read(run):
    return run.bucket_warmup_s
