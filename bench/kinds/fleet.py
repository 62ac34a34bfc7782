"""The ``fleet`` kind: a fleet of unrouted links, each deciding its own
ToggleCCI. A configuration that names no ``kind`` is one of these.

A kind is what the harness needs of a configuration besides its sizes. Each
``bench/kinds/<kind>.py`` holds all six names below, and a configuration's
``"kind"`` key leads to it:

* ``build(config, seed)``: the scenario the traffic's driver is handed. Its
  ``.demand`` is the year of demand as ``(rows, hours)``, and a driver counts
  each call's ``rows x hours`` decided as the run's ``row_hours``, so
  ``row_hours_per_s`` and ``device_ns_per_row_hour`` read rows as the kind
  defines them: links here, pairs for a routed kind (pair-hours per second);
* ``reference(built, hours)``: the plain float64 reference over the first
  ``hours`` hours, the planes the driver's ``checks`` take;
* ``SUT``: the module through which the drivers reach the program, and
  ``CONTROL``: the one that puts the lower-precision control in its place;
* ``TINY``: the configuration's keys and values at the size the benchmark's
  CPU tests run;
* ``FAULTS``: the names of the program's timed paths that this kind runs,
  each a planter ``bench/faults/<name>.py`` whose ``plant(monkeypatch,
  fault)`` breaks that path for ``bench/tests/test_faults.py``: here
  ``chunk_program`` (``repro.fleet.runtime._build_step_many``) and
  ``plan_program`` (``repro.fleet.engine._run_plan``).

This one only names ``bench.scenario``, ``bench.reference``, ``bench.sut``
and ``bench.control``.
"""
from __future__ import annotations

from bench import control, reference as _reference, scenario, sut

SUT, CONTROL = sut, control
TINY = {"n_links": 8, "horizon": 480, "hours_per_month": 120}
FAULTS = ("chunk_program", "plan_program")

build = scenario.build


def reference(built: scenario.Fleet, hours: int) -> dict:
    return _reference.run(scenario.LinkArrays(built.links, built.hours_per_month),
                          built.demand[:, :hours])
