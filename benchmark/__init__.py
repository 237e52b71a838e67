"""The benchmark: data-driven cells measured on the chip.

``BENCHMARK.json`` at the root names every cell, configuration and metric;
this package finds each part by that name (``benchmark/cells.py``):

- ``configs/<config>.json``: a deployment's sizes, source and cuts;
- ``traffic/<traffic>.json``: a traffic mix: the query and the loop;
- ``queries/<query>.py``: a query driver: seeded generation, the call into
  the program, the rows a query counts, the plain reference and its control;
- ``loops/<loop>.py``: how the window offers the query (a closed power loop);
- ``metrics/<metric>.py``: one reader per metric, end to end or per layer;
- ``peaks.json``: published peaks keyed by ``device_kind``.

A later PR adds a cell by adding files and entries; it edits none of these.
"""
