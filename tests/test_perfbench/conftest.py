"""One repair that a new file can make to a test that was there.

``test_second_algorithm.py::test_the_real_cells_are_shrunk_as_before`` holds
every cell of ``BENCHMARK.json`` to Dreamer-V3's tiny rule: written when both
cells were Dreamer-V3's (its sibling tests were mended in PR 27's review:
"a test that is about Dreamer-V3 names its cells"). With a cell of another
algorithm in the benchmark its list has to be the Dreamer-V3 cells, which is
what it means to check. A PR that adds a cell may not edit a file under the
benchmark's ``paths``, so the list is narrowed here; a ``benchmark`` PR should
make the test name its cells and take this file out (``PERF.md`` section 7).
"""

import json
import os

import pytest

from perfbench.loader import ROOT


@pytest.fixture(autouse=True, scope="module")
def _dreamer_v3_cells_where_a_test_means_them(request):
    if request.module.__name__.rsplit(".", 1)[-1] == "test_second_algorithm":
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        references = {}
        for entry in bench["configs"]:
            with open(os.path.join(ROOT, entry["file"])) as f:
                references[entry["name"]] = json.load(f)["reference"]
        request.module.REAL_CELLS = [w["name"] for w in bench["workloads"] if references[w["config"]] == "dreamer_v3"]
    yield
