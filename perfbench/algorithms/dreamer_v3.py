"""Dreamer-V3, as ``"reference": "dreamer_v3"`` in a configuration's file
names it. The harness takes three names from such a module: ``Capture(cfg,
seed)`` (what is recorded of the program's run; ``calls`` and ``placement``
are read by the harness), ``installed(capture)`` (a context manager around the
program's run that hands it the seeded weights and records) and
``verify(cfg, seed, capture, limits, stamps)``."""

from perfbench.bridge import Capture, installed  # noqa: F401
from perfbench.correct import verify  # noqa: F401
