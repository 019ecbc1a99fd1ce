"""The Table II lake: the generator of ``harness/lake.py``, the screen
mix's base tables of ``harness/traffic.py`` and the reference of
``harness/reference.py``.  Its chunks are NumPy arrays made on the host.

A configuration names its lake with ``"lake": "<name>"`` (``table2``
where it names none), and the harness loads ``bench/lakes/<name>.py``.
A lake module supplies:

* ``Lake(cfg, seed)``: the candidate columns, with ``columns``,
  ``discrete`` (a host bool array, one entry a column),
  ``chunk(c0, c1, device)`` and ``keep_rows()``.  ``chunk`` gives
  columns [c0, c1) as a dict of ``(c1 - c0, rows)`` arrays: ``keys``
  (uint32 key hashes), ``vals_f`` (float32 values) and ``vals_c``
  (integer categorical codes).  They are NumPy arrays, or
  ``jax.Array``s committed to ``device``, the cell's chip that the
  harness gives the chunk (None outside a cell's run);
* ``requests(lake, spec, warm=False)``: the mix's base tables, each as
  its list of target queries;
* ``check_options(gates)``: called once at set-up with the mix's gates
  that depart from the service's defaults (``traffic.gates``); raises
  ``ValueError`` for one the reference cannot hold the service to;
* the reference: ``LakeSketches(lake, n)``, ``join(ls, q, n, joins)``
  (a tuple whose last entry is the join size of every column),
  ``kind_of(cand_discrete, target_discrete)``,
  ``answer(lake, ls, q, *, n, k, min_join, only=None, dtype="float32",
  joins=None, **gates)`` (a ``reference.Answer``, indexed by column
  number) and ``ranked(ans, *, top_k, min_join)``.

How a chunk reaches the service (``harness.cell.ingest``): whole, in
one call, where the service has ``add_columns``::

    svc.add_columns(tables, "k", "v", keys, vals_f, vals_c, discrete)

``tables`` the chunk's table names ``c<column>``, ``keys``, ``vals_f``
and ``vals_c`` the chunk's arrays as ``chunk`` made them (on the host or
on their chip, never sliced by the harness), and ``discrete`` its slice
of ``Lake.discrete``.  It stands for the calls
``svc.add(tables[j], "k", "v", keys[j], vals_c[j] if discrete[j] else
vals_f[j], bool(discrete[j]))`` in order, which the harness makes
itself, from a host copy of the chunk, where the service has no
``add_columns``.
"""

from __future__ import annotations

from harness.lake import Lake  # noqa: F401
from harness.reference import (LakeSketches, answer,  # noqa: F401
                               check_options, join, kind_of, ranked)
from harness.traffic import requests  # noqa: F401
