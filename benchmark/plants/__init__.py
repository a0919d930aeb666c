"""Families.  A configuration (``benchmark/configs/<config>.json``) names one
module of this package under ``plant.module`` and its parameters under
``plant.params``; the module exposes

    Plant(seed, n_docs, params)   starts the front and sequencer

with ``port`` (the child's ``--port``), ``doc_ids``, ``ops`` (rows sequenced
so far == rows the device has to apply) and ``nacks``, and the methods

    join(doc_id, n_writers)
    edit(doc_id)                  one local edit == one device row, unsent
    flush(doc_id) -> int          submit what is unsent, then deliver
    drained(doc_ids, deadline)    the front has handed everything to the kernel
    verify(final, touched, first, sample_seed, budget_s, min_sample)
                                  -> {"ok": bool, "why": str, ...}
    stop()

``final`` is the child's ``done`` line.  What the fleet itself needs for the
family (``--family tree``) goes into the configuration's ``fleet_main_flags``.
A new family is a new module here plus the configuration that names it."""
