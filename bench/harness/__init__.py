"""The benchmark's harness: cell lookup, fleet set-up, the measured window,
the trace reduction, the work count, and the check that decides ``correct``."""
