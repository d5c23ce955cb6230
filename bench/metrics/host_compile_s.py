"""Seconds in the program's compiler for all tenants at set-up (an
artifact-store hit included)."""


def read(run):
    return run.host_compile_s
