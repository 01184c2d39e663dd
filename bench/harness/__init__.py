"""The on-chip benchmark's harness: one general driver for every cell.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``spec``):

* ``bench/configs/<config>.json``  — the model as run, with the plain
  reference it names (``bench/configs/ref_*.py``) and the architecture
  module of its ``model_type`` (``bench/configs/arch_<model_type>.py``:
  its mapping onto the program's config, its leaf rules, its work per
  layer, its kernels and its test size; see ``spec``) beside it;
* ``bench/traffic/<traffic>.json`` — parameters that ``traffic`` turns into
  a request schedule;
* ``bench/metrics/<metric>.py``    — a small reader of one per-layer metric.

The modules here hold the yardstick that later changes to the program may
not move: traffic generation (``traffic``), the weights made from the seed
(``weights``), the load driver and its clocks (``load``), the work that the
served tokens need (``work``), the trace reduction (``trace``), the table of
device peaks (``peaks.json``) and the comparison that decides ``correct``
(``correct``). From the program under test the harness takes only the
serving engine, its spans and counters, and its kernels' names.
"""
