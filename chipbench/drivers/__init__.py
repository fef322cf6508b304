"""One module per kind of traffic, named by a traffic file's ``driver``.

A driver owns everything that is particular to its cells: how their inputs
are made, how the program is set up and driven, and which numbers decide
``correct``. The harness (``chipbench.run``, ``chipbench.readings``) knows
nothing of either. Every driver module declares:

* ``CHECKS``: a tuple of the names of the numbers ``check`` returns; a
  cell's limits file (``chipbench/limits/<cell>.json``) gives a limit for
  each, and for no other;
* ``inputs(config, seed, scale)``: the inputs that the program, its plain
  reference and the control are handed, made from the configuration and
  ``--seed``; ``scale`` below 1 shrinks them for CPU tests;
* ``build_tuner(traffic)``: the program's tuner, built once per process
  (``None`` where the program has none);
* ``Program(config, traffic, seed, tuner, scale)``: set-up, which warms up
  every shape the window uses. The object exposes ``about`` (one line on
  what runs), ``metrics`` (set-up's end-to-end readings in seconds, with
  ``tune_s``), ``nnz``, ``n_rows`` and ``n_cols`` (the SpMV the roofline is
  taken of), ``request(i)`` (answers the window's ``i``-th request; the
  answer has ``spmvs``, the SpMVs it answered to the caller) and
  ``release()`` (frees the program's state and returns what ``inputs``
  made, for the check);
* ``check(inputs, answers, traffic)``: for each answer, a dict of the
  ``CHECKS`` numbers against the plain reference; larger is worse;
* ``control(inputs, config, traffic, seed, count)``: the first ``count``
  requests answered by the reference at the precision below the one the
  configuration states, in the program's place.
"""

from __future__ import annotations

FUNCTIONS = ("inputs", "build_tuner", "Program", "check", "control")
PROGRAM_METHODS = ("request", "release")


def contract_faults(driver, limits: dict) -> list[str]:
    """Where ``driver`` breaks the contract above, or a cell's ``limits``
    name other numbers than its ``CHECKS``; empty where it keeps it."""
    faults = [f"no callable {name}" for name in FUNCTIONS
              if not callable(getattr(driver, name, None))]
    program = getattr(driver, "Program", None)
    if program is not None:
        faults += [f"Program has no method {name}" for name in PROGRAM_METHODS
                   if not callable(getattr(program, name, None))]
    checks = getattr(driver, "CHECKS", None)
    if not (isinstance(checks, tuple) and checks and all(isinstance(c, str) for c in checks)):
        faults.append("CHECKS is not a tuple of names")
    elif set(limits) != set(checks):
        faults.append(f"the limits name {sorted(limits)}, CHECKS {sorted(checks)}")
    return faults
