"""A benchmark cell's layer files for a rehearsal, found by what lists the
cell and by what each file reads, never by a file's name: a `benchmark` PR
that merges a kit-prefixed repeat into its original (`pppoe.gen_share` into
`gen.share`: PERF.md section 7 row 0) renames what a cell reports and edits
no rehearsal."""

import json
import os

from benchmark.lib import layers


def listed(bench_dir: str, cell: str) -> list[dict]:
    """The layer files that list `cell`."""
    return [m for m in layers.layer_files(bench_dir) if cell in m["cells"]]


def reading(files: list[dict], **read) -> str:
    """The name of the one file among `files` whose `read` holds `read`."""
    hit = [m["name"] for m in files
           if all(m["read"].get(k) == v for k, v in read.items())]
    assert len(hit) == 1, (read, hit)
    return hit[0]


def stand_in(bench_dir: str, cell: str, tiny: str) -> list[dict]:
    """Every file of `bench_dir` that lists `cell` lists `tiny` too from now
    on; the files, as they stood."""
    files = listed(bench_dir, cell)
    assert files
    for m in files:
        with open(os.path.join(bench_dir, "layers", m["name"] + ".json"),
                  "w") as f:
            json.dump(dict(m, cells=m["cells"] + [tiny]), f)
    return files


# what the loop's generic files read, whatever they are called in a cell
GEN_SHARE = dict(kind="bench_span", span="gen", stat="share_of_window")
LOOP_US = dict(kind="bench_span", span="drive_once", stat="sum_per_frame")
BEAT_P99 = dict(kind="bench_span", span="beat", stat="p99")
STEP_P50 = dict(kind="trace_program", pick="longest", stat="p50")
TICK_MS = dict(kind="counter", path="engine.trace.stage_ns.slow_path")
UPLOAD_CALLS = dict(kind="counter", path="engine.trace.xfer.upload_calls")
FETCH_CALLS = dict(kind="counter", path="engine.trace.xfer.fetch_calls")
PREFETCH_CALLS = dict(kind="counter", path="engine.trace.xfer.prefetch_calls")


def counter(path: str) -> dict:
    return dict(kind="counter", path=path)

