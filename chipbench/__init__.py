"""On-chip benchmark of the spherical k-means system.

One harness (``chipbench.run``) drives every cell that ``BENCHMARK.json``
names.  A cell is a configuration file (``chipbench/configs/``), a traffic
mix (``chipbench/traffic/<name>.json``, the parameters of the driver it names,
``chipbench/drivers/<driver>.py``) and the limits of its correctness check
(``chipbench/limits/<cell>.json``).  Each per-layer metric is a reader of its
own (``chipbench/metrics/<metric>.py``).  The harness finds all of them by
the names in ``BENCHMARK.json`` and the traffic files, so a new
configuration, mix, driver or metric is new files plus new entries.

The yardstick lives here, apart from the program under test: the corpus
generator, the plain reference, the peak table, the least-time functions and
the trace reduction.  None of them computes anything with ``repro`` code.
"""
