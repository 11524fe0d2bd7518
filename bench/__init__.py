"""The on-chip benchmark: one command, cells defined by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
configuration, traffic mix or per-layer metric is a file of its own,
found by the name the cell gives:

* ``bench/configs/<config>.json``  the model as it is run;
* ``bench/traffic/<traffic>.json`` parameters of the hole-trace generator
  ``bench/traffic/holes.py``;
* ``bench/metrics/<name>.py``      a reader with ``read(run) -> float|None``;
* ``bench/limits/<cell>.json``     the limit of each number the check
  compares (``bench/check.py``).
"""
