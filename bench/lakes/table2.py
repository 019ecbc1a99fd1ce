"""The Table II lake: the generator of ``harness/lake.py``, the screen
mix's base tables of ``harness/traffic.py`` and the reference of
``harness/reference.py``.

A configuration names its lake with ``"lake": "<name>"`` (``table2``
where it names none), and the harness loads ``bench/lakes/<name>.py``.
A lake module supplies:

* ``Lake(cfg, seed)``: the candidate columns, with ``columns``,
  ``discrete``, ``chunk(c0, c1)`` (key hashes, float and categorical
  values of columns [c0, c1)) and ``keep_rows()``;
* ``requests(lake, spec, warm=False)``: the mix's base tables, each as
  its list of target queries;
* ``check_options(gates)``: called once at set-up with the mix's gates
  that depart from the service's defaults (``traffic.gates``); raises
  ``ValueError`` for one the reference cannot hold the service to;
* the reference: ``LakeSketches(lake, n)``, ``join(ls, q, n, joins)``,
  ``kind_of(cand_discrete, target_discrete)``,
  ``answer(lake, ls, q, *, n, k, min_join, only=None, dtype="float32",
  joins=None, **gates)`` and ``ranked(ans, *, top_k, min_join)``.
"""

from __future__ import annotations

from harness.lake import Lake  # noqa: F401
from harness.reference import (LakeSketches, answer, join, kind_of,  # noqa: F401
                               ranked)
from harness.traffic import requests  # noqa: F401


def check_options(gates: dict) -> None:
    """This reference has no phase-0 containment gate, so it takes the
    service's defaults only."""
    for key, value in gates.items():
        raise ValueError(
            f"lake table2 refuses {key}={value!r}: its reference has no "
            f"phase-0 containment gate, so a mix on it leaves {key} out")
