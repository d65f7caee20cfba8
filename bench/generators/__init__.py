"""General traffic generators, one module each, named by a traffic file.

A traffic file (``bench/traffic/<mix>.json``) names its generator under
``"generator"``; ``bench.spec.load_cell`` finds ``<generator>.py`` in this
directory and hands the module to the cell as ``cell.generator``. A
generator defines every function of ``CONTRACT``:

* ``setup(cell, seed) -> prep``: the data and the program's state on the
  chips the cell asks for, made from ``seed``, with every program the
  window runs compiled or loaded from the persistent cache (timed as
  set-up). ``prep.x`` and ``prep.y`` are the training data.
* ``measure(prep, seconds) -> window``: the traffic for about ``seconds``,
  inside a ``bench.window`` profiler span. The window has ``seconds``,
  ``steps``, ``attempted`` and ``failed``, ``histories`` (each fit's
  history as ``repro.core.fit`` returns it) and ``outputs`` (what
  ``bench.check.fit_numbers`` reads of each fit).
* ``compare(prep, window, limits) -> list[bench.check.Compared]``: every
  number the plain reference compares, each beside its limit.
* ``program_temp_bytes(prep) -> int``: the most temporary device memory
  that one of the window's programs asks for on a chip.
* ``window_programs(cell) -> list[jax.stages.Lowered]``: the programs the
  window runs whose instructions carry the ``gp.*`` scopes, lowered for
  the cell's shapes (and shardings) as the window runs them.
  ``bench.scopes`` compiles them anew and reads each instruction's
  ``op_name`` from the compiled text.
"""
CONTRACT = ("setup", "measure", "compare", "program_temp_bytes",
            "window_programs")
