"""XLA programs the process built or loaded from the persistent cache by the
end of the run (``repro.launch.compile_cache.counts``); None where the
program does not count them."""


def read(run):
    try:
        from repro.launch.compile_cache import counts
    except ImportError:
        return None
    programs = counts()["programs"]
    return programs or None
