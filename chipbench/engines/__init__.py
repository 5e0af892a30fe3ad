"""Engine drivers, one file per engine, found by the configuration's
``engine`` key. Each defines ``NUMBERS`` (the names its check reads) and a
``Cell(config, traffic, seed, policy=None, devices=None)``:

* ``config``, ``traffic``: the cell's configuration and traffic files;
  ``seed``: the run's ``--seed``; ``policy``: the program's storage
  policy, ``"bf16"`` for the control;
* ``devices``: the JAX devices of the cell, exactly the ``chips`` its
  entry in ``BENCHMARK.json`` asks for, which ``run.run_cell`` has checked
  are there. A multi-chip engine builds its mesh over these and no
  others; a one-chip engine may ignore them (JAX's default device is that
  chip);

and with:

* ``call(i)``: call ``i`` of the closed loop through the program's public
  ``run_*`` entry, returning before ``block_until_ready``;
* ``keep(out)``: the part of an answer the check reads;
* ``free()``: drop the program's device state;
* ``check([(i, kept), ...])``: one dict of ``NUMBERS`` per kept call,
  against the plain reference;
* ``agent_rounds_per_call``, ``rounds_per_call`` and ``work``: the work
  functions of ``chipbench/work`` a round runs, each with its arguments.

A new cell is added by new files and new entries of ``BENCHMARK.json``
alone, and edits none:

* its configuration ``chipbench/configs/<config>.json`` (with its entry
  under ``configs``), where the cell needs a new one;
* its traffic mix ``chipbench/traffic/<traffic>.json``;
* its check ``chipbench/workloads/<workload>.json``: the calls compared
  and the limit of each number (and its entry under ``workloads``);
* a sizes file ``chipbench/tests/sizes/<stem>.json`` for each new
  configuration or traffic file ``<stem>.json``: the keys the CPU tests
  cut, ``{}`` where it is small already;
* for a new engine, its driver ``chipbench/engines/<engine>.py``, its
  faults file ``chipbench/tests/faults/<engine>.py`` (``FAULTS``, the
  faults its timed path can have, each ``(monkeypatch, workload) -> hook
  | None``), and its plain reference under ``chipbench/reference/`` where
  no existing one fits;
* each new per-layer metric ``chipbench/metrics/<name>.py`` (a ``read(ctx)``
  of :class:`chipbench.trace.Context`) and its entry under ``per_layer``.
"""
