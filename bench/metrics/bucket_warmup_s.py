"""Seconds to serve every batch size of every tenant once at set-up: the
first call of each tenant's forward at each bucket, compiled or loaded from
the compile cache (the batch lane pads and slices on the host, with no
program of its own)."""


def read(run):
    return run.bucket_warmup_s
