"""The on-chip benchmark of the LITS string index served through
``IndexService``: corpus, YCSB traffic, reference map, trace reduction and
the run itself.  Everything that decides a measurement lives here, apart
from the program under test (``src/repro``)."""
