"""Engine drivers, one file per engine, found by the configuration's
``engine`` key. Each defines ``NUMBERS`` (the names its check reads) and a
``Cell(config, traffic, seed, policy=None)`` with:

* ``call(i)``: call ``i`` of the closed loop through the program's public
  ``run_*`` entry, returning before ``block_until_ready``;
* ``keep(out)``: the part of an answer the check reads;
* ``free()``: drop the program's device state;
* ``check([(i, kept), ...])``: one dict of ``NUMBERS`` per kept call,
  against the plain reference;
* ``agent_rounds_per_call``, ``rounds_per_call`` and ``work``: the work
  functions of ``chipbench/work`` a round runs, each with its arguments.
"""
