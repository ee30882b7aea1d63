"""On-chip benchmark of Heta's training path (``python bench/run.py``).

Everything that measures lives here: the datasets the cells train on, the
plain reference that decides ``correct``, the trace reduction, the FLOP and
byte counts, the peaks table and one reader per metric.  The system under
test is imported from ``src/repro`` of the same checkout; nothing here is
imported by it.
"""
