"""Stand-ins for cells added since test_benchmark.py's list was written.

`tiny_dir` (test_benchmark.py) copies the benchmark and gives every layer
file's cells a tiny stand-in through the literals `TINY_CELLS`, `TINY_ARGV`
and `BASE_OF`: a layer file that names a cell those lack stops the fixture
with a KeyError, and with it every rehearsal. A PR that adds a cell may add
files here and may not edit one, so the new cell's stand-in is added to
those literals from this file, before the fixture reads them (PR 32 did the
same; PR 36 moved its entries into test_benchmark.py and deleted its file).
The next `benchmark` issue moves these entries too and deletes this
(PERF.md section 7 row 1 xvii).
"""

import sys

import pytest

QINQ = ["--pppoe-enabled", "--pppoe-auth", "none", "--qinq-enabled"]


@pytest.fixture(scope="module", autouse=True)
def cells_added_since_have_stand_ins():
    tb = sys.modules.get("test_benchmark")
    if tb is None:  # a module here that does not rehearse through tiny_dir
        return
    # 4,096 subscribers behind a pair each, 128 NAT subscribers of whom the
    # kit's default makes a quarter PPPoE
    tb.TINY_ARGV.setdefault("tiny-qinq", tb.TINY_ARGV["tiny-wire"] + QINQ)
    tb.BASE_OF.setdefault("tiny-qinq", "qinq-pppoe-cgnat-1M-wire")
    tb.TINY_CELLS.setdefault(
        "tiny-qinq.flood",
        ("qinq-pppoe-cgnat-1M-wire.flood-64B", "tiny-qinq", "tiny-flood"))
