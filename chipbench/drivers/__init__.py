"""Drivers: each turns a traffic file into work for the program.

A traffic file (``chipbench/traffic/<mix>.json``) names its driver under
``"driver"`` and gives that driver's parameters.  The harness loads the
driver from ``chipbench/drivers/<driver>.py`` by that name, so a new mix of
an existing driver is a new data file, and a new driver is a new file here.

A driver module defines ``Driver(config, traffic, seed, span, log)`` with:

- ``setup(seconds)``: build the cell's inputs from the seed and warm every
  program the window runs;
- ``window(seconds)``: the measured work;
- ``end_to_end()``: {metric: value} of the cell's end-to-end metrics other
  than ``setup_s``;
- ``counts()``: (attempted, failed);
- ``record()``: what the per-layer metric readers read;
- ``release()``: free the program's state on the device;
- ``numbers()``: after ``release``, the numbers the correctness check
  compares, running the plain reference;
- ``close()``: stop whatever the driver started.
"""
from __future__ import annotations

import numpy as np


def sub_seeds(seed: int) -> dict:
    """Independent seeds of a run's corpus, fit and traffic."""
    a, b, c = np.random.SeedSequence(seed).generate_state(3)
    return {"corpus": int(a), "fit": int(b) & 0x7FFFFFFF, "traffic": int(c)}
