"""The benchmark: the yardstick every later PR is measured with.

``BENCHMARK.json`` at the root of the repo lists configurations, cells and
metrics; everything a cell, a traffic mix or a per-layer metric needs sits
in a file of its own under this directory, found by its name:

* ``configs/<config>.json`` — one configuration's sizes, with its plain
  reference (``configs/<config>_reference.py``, imports nothing of the
  program) and the adapter that drives the program for it
  (``configs/<config>_program.py``);
* ``traffic/<mix>.json`` — one traffic mix; its ``kind`` names the driver
  ``drivers/<kind>.py``;
* ``metrics/<metric>.json`` — one per-layer metric; its ``reader`` names
  ``readers/<reader>.py``;
* ``peaks.json`` — the chip's published peaks by ``device_kind``;
* ``ops/`` — operations and bytes from shapes; ``trace.py`` — the
  reduction from the profiler's trace to busy/idle, op time and gaps;
  ``refmath.py`` — the arithmetic the plain references share.

No list of cells, configurations or metrics lives in code.
"""
