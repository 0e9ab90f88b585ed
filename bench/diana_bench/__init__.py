"""The on-chip benchmark of the DIANA scheduler: generators, the plain
references, the trace and sample reductions, and the harness that runs
one cell (``bench/run.py``)."""
