"""Traffic generators.  A traffic file (``benchmark/traffic/<mix>.json``) names
one module of this package under ``generator``; the module exposes

    doc_rates(params, n_docs, seed) -> numpy array of ops/s per document index
    schedule(params, n_docs, seconds, seed, stream) -> (tick index per op, document index per op)

and the harness does the rest.  A new arrival process is a new module here; a
new mix of an existing process is a data file only."""
