"""Serving paths: how a configuration is set up, driven through one window
of traffic, and checked against its plain reference.

A configuration's file names its path under ``"path"``; where it names
none the path is ``fleet``.  The harness finds the path by its file,
``paths/<name>.py`` (``spec.path``), so a path is added by adding its file,
and ``harness/cell.py`` knows none.  A path module provides four
functions:

``set_up(cfg, device) -> setup``
    Everything made ready to serve: weights or data from the
    configuration's seed, programs compiled, every shape the traffic uses
    warm.  It is timed as set-up.  ``device`` is the run's device dict
    (``platform``, ``kind``, ``count``).  The ``setup`` object is what the
    test-only ``faults`` hook of ``cell.run`` receives, to break the served
    path underneath.

``rows(setup, seed) -> pool``
    The inputs this seed sends, made before the window.

``measure(setup, cell, traffic, pool, seed, seconds, traced, t_start) -> run``
    One window of ``traffic`` (the mix's JSON) of ``seconds``, with
    ``t_start`` the process's start on ``time.perf_counter``.  Nothing
    compiles inside it.  With ``traced`` the window runs inside
    ``harness.session.Session``, whose marks open and close it in the
    profiler's trace.

``numbers(run, pool, control=False) -> (nums, attempted, failed)``
    After the window: the compared numbers, keyed as the configuration's
    ``limits`` are, for every answer (or a seeded sample of them) against
    the plain reference; the requests sent inside the window; those of
    them never answered.  With ``control`` the numbers are the control's,
    the reference one precision lower, for the same requests.

The ``run`` carries what the readers that know no path read, so that
``served_rps``, ``setup_s`` and ``device_idle_pct`` read a run of any path
unchanged:

* ``seconds``: the window's length;
* ``setup_s``: seconds from ``t_start`` to the start of the traffic;
* ``config``: the configuration's dict;
* ``peaks``: the chip's peaks (``harness.peaks.peaks(device["kind"])``);
* ``trace``: the session's ``summary``, a ``trace.Summary``, or None;
* ``log.t_done``, ``log.due``: arrays over the requests, in seconds from
  the window's open; ``t_done`` is NaN for a request never answered;
* ``in_window(t)``: the mask of times ``t`` inside ``[0, seconds]``.

A path's own per-layer readers may read more of its ``run``; a reader
that finds nothing to read returns None.
"""
