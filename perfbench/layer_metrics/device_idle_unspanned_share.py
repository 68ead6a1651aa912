"""Share of the traced stretch's idle time at instants that no span of the
program covers, on any thread, but a window span (``Time/*``), and at which
env 0 is not in ``step()``: what no span yet names. Every span the run
emitted counts, read from ``telemetry.jsonl`` and clipped to the stretch
(``span_tree.idle_by_innermost``), so a span still open when the profiler
stopped counts too."""

from perfbench import span_tree


def read(run):
    table = span_tree.idle_by_innermost(run)
    if not table or not sum(table.values()):
        return None
    return 100.0 * span_tree.unspanned_seconds(table) / sum(table.values())
