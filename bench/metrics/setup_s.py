"""Set-up: process start to the start of the traffic (JAX start, the
tenants' compiles through the artifact store, every batch size served)."""


def read(run):
    return run.setup_s
