"""On-chip benchmark harness: one cell of ``BENCHMARK.json`` per run.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the sizes as run, plus ``driver`` (which
  file under ``drivers/`` runs this kind of system) and ``reference``
  (the plain reference beside it, ``configs/<config>.reference.py``);
* ``traffic/<traffic>.json`` — the parameters the general generator in
  :mod:`chipbench.traffic` reads, and the limits of the check;
* ``metrics/<metric>.py`` — one reader per per-layer metric, with
  ``read(ctx) -> float | None``.
"""
