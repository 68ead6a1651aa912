"""The benchmark of sheeprl_tpu: ``python3 perfbench/run.py --workload <cell> ...``.

Everything the yardstick needs lives in this directory (see README.md); from
the program it takes only ``sheeprl_tpu.cli.run`` and what a run leaves behind.
"""
