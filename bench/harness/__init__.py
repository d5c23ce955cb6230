"""The benchmark's harness: cell, configuration and serving-path lookup,
the traced window, the fleet path's helpers, the trace reduction, the work
count, and the check that decides ``correct``.  The serving paths
themselves are in ``paths/``."""
