"""One module a traffic kind. Each supplies

* ``setup(run) -> state``: data from the seed, then ONE warm-up call of
  each shape the window uses, under ``run.phases``;
* ``window(run, state, seconds) -> produced``: the measured work; fills
  ``run.facts`` with the cell's end-to-end numbers, ``attempted`` and
  ``failed``;
* ``release(run, state)``: frees what the program holds on the device;
* ``check(run, produced) -> {name: {"value", "limit"}}``: the plain
  reference and the comparison, run after the window on what it produced.
"""
