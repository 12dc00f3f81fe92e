"""Share of the K-sharded mesh step's device time spent in collectives.

On every chip, the device time of the collective operations inside the
executables named ``jit_mesh_step`` in the traced window, over the time of
those executables, summed over the chips, in percent.  An operation is
known by its name, the HLO instruction's: XLA's own (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``, ``all-to-all``
and their async halves) or, for an all-reduce JAX emitted, the primitive's
(``psum``, ``pmax``, ``pmin``)."""

STEP = "jit_mesh_step"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "psum", "pmax", "pmin")


def read(record):
    tr, w = record.get("trace"), record.get("window")
    if tr is None or w is None or not record.get("fits"):
        return None
    step_ns = coll_ns = 0.0
    for dev in range(len(tr.devices)):
        steps = [m for m in tr.modules_in(*w, device=dev)
                 if m[0].startswith(STEP)]
        for _, ms, me in steps:
            step_ns += me - ms
            coll_ns += sum(min(e, me) - max(s, ms)
                           for name, s, e in tr.devices[dev].ops
                           if name.startswith(COLLECTIVES)
                           and e > ms and s < me)
    return 100.0 * coll_ns / step_ns if step_ns > 0 else None
