"""One module per algorithm, found by the ``reference`` key of a
configuration's file: the bridge into the program, the comparison that decides
``correct``, the FLOP count and the trace tables (README.md, "What an algorithm
module supplies")."""
