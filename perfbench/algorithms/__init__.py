"""One module per algorithm, found by the ``reference`` key of a
configuration's file: what the harness needs of it to decide ``correct``."""
