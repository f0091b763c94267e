"""The system under test: the app `bng run` builds, and what the harness
reads of it whichever deployment it serves.

Copied from chip_smoke.py (PR 22), which proved these calls on the chip:
build the app from a configuration's argv, then read its idleness, its
counters and its selectors. The benchmark may not import chip_smoke.py (a
later PR may edit it), so the functions live here.

An app has one of three shapes, read from what it holds and from the loop
`cli.py _drive_beat` picks for it:

  cluster    `--shards N`: the sharded loop over the steered native ring
  scheduler  `--scheduler-enabled` over a ring with `rx_pop` (PyRing)
  engine     neither, or a scheduler in front of a native ring, which the
             loop bypasses: `Engine.process_ring_pipelined`, the loop a
             `--wire-if` deployment runs

What belongs to one deployment -- the addresses, the provisioning, the
frames and the plain reference they are held against -- is a kit
(`benchmark/kits/<name>.py`), named by the configuration's `kit` key.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
DEFAULT_KIT = "ipoe"
SHAPES = ("cluster", "scheduler", "engine")


class BenchError(RuntimeError):
    """Set-up did not reach the state the cell states."""


def load_named(kind: str, name: str, bench_dir: str = BENCH_DIR) -> dict:
    """configs/<name>.json, traffic/<name>.json or layers/<name>.json."""
    path = os.path.join(bench_dir, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_kit(config: dict, bench_dir: str = BENCH_DIR):
    """The module kits/<config["kit"]>.py of `bench_dir`; without the key,
    the kit that was the harness's own code until PR 27."""
    name = config.get("kit", DEFAULT_KIT)
    path = os.path.join(bench_dir, "kits", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"configuration {config.get('name')!r} names kit "
                         f"{name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark.kits.{name}", path)
    kit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kit)
    return kit


# --------------------------------------------------------------------------
# build: the app `bng run` builds
# --------------------------------------------------------------------------

def run_argv(config: dict) -> list[str]:
    from bng_tpu.utils.net import ip_to_u32, u32_to_ip

    argv = list(config["argv"])
    pub = config.get("nat_public_ips")
    if pub:
        base = ip_to_u32(pub["base"])
        argv += ["--nat-public-ips",
                 *(u32_to_ip(base + i) for i in range(int(pub["count"])))]
    return argv


def build_app(config: dict):
    from bng_tpu import cli

    parser = argparse.ArgumentParser()
    cli._add_run_flags(parser)
    app = cli.BNGApp(cli._config_from_args(parser.parse_args(run_argv(config))))
    # the in-memory ring is built for a packet source; the generator
    # itself is switched off so that the benchmark pushes every frame
    app.config.synthetic_subs = 0
    return app


# --------------------------------------------------------------------------
# the app's shape, and what is read through it
# --------------------------------------------------------------------------

def shape(app) -> str:
    """Which of the three loops `drive_once` runs for this app, by the
    conditions `cli.py _drive_beat` tests. An app that fits none is refused
    by what it holds, not by a KeyError further down."""
    c = app.components
    if "cluster" in c:
        return "cluster"
    if "scheduler" in c and hasattr(c.get("ring"), "rx_pop"):
        return "scheduler"
    if "engine" in c and "ring" in c:
        return "engine"
    raise BenchError(f"an app of a shape the harness does not know: it holds "
                     f"{sorted(c)}, and none of {SHAPES} fits (lib/app.py shape)")


def _tables_owner(app):
    """The object whose `tables` the device serves from, and whose
    `_inflight` is the pipelined loops' window."""
    c = app.components
    return c["cluster"] if shape(app) == "cluster" else c["engine"]


def table_leaves(app):
    return jax.tree_util.tree_leaves(_tables_owner(app).tables)


def idle(app) -> bool:
    c = app.components
    if c["ring"].rx_pending():
        return False
    if shape(app) != "scheduler":  # the pipelined loops: a window in flight, or none
        return _tables_owner(app)._inflight is None
    snap = c["scheduler"].stats_snapshot()
    return not any(snap[lane]["queue_depth"] or snap[lane]["inflight"]
                   for lane in ("express", "bulk"))


def counters(app) -> dict:
    """Everything a `counter` layer metric or the count check reads, as
    one nested dict of plain numbers."""
    from bng_tpu.ops.dhcp import ST_HIT
    from bng_tpu.ops.qos import QST_PKTS_DROPPED
    from bng_tpu.telemetry import spans as tele

    c = app.components
    kind = shape(app)
    out = {"ring": dict(c["ring"].stats())}
    if kind == "cluster":
        cl = c["cluster"]
        st = {k: np.asarray(cl.stats.get(k, np.zeros(16, np.uint64)))
              for k in ("dhcp", "qos")}
        out["sharded"] = cl.telemetry.snapshot()
        out["sharded"].pop("merged_stages", None)
        out["engine"] = {"dropped": int(out["ring"].get("drop", 0)),
                         "slow_errors": int(cl.stats["slow_errors"])}
    else:
        es = c["engine"].stats
        st = {"dhcp": es.dhcp, "qos": es.qos}
        out["engine"] = {k: int(getattr(es, k)) for k in
                         ("batches", "tx", "fwd", "dropped", "passed",
                          "slow_errors")}
    if kind == "scheduler":
        out["sched"] = c["scheduler"].stats_snapshot()
        # the snapshot's occupancy_avg is over the process's life; the sum
        # beside `batches` lets a layer file take the window's own
        out["sched"]["bulk"]["occupancy_sum"] = float(
            c["scheduler"].bulk.stats.occupancy_sum)
    elif kind == "engine":
        # the engine keeps no snapshot of its own: the Tracer's sums, as
        # `sched.trace` and `sharded.trace` carry them on the other loops
        out["engine"]["trace"] = tele.trace_sums()
    host = c["dhcp"].stats
    # the antispoof kernel's own drop counter is not here: it counts
    # network-side and padding lanes too, which the pipeline never drops
    out["device"] = {"dhcp_hit": int(st["dhcp"][ST_HIT]),
                     "qos_dropped": int(st["qos"][QST_PKTS_DROPPED])}
    out["host"] = {"dhcp_handled": int(host.discover + host.request)}
    return out


def selectors(app) -> str:
    """The implementation selectors this run was served by."""
    import bng_tpu.ops.qos as qos_mod

    c = app.components
    kind = shape(app)
    ring = type(c["ring"]).__name__
    if kind == "cluster":
        return f"table={c['cluster'].table_impl} ring={ring} sharded"
    eng = c["engine"]
    head = (f"table={eng.table_impl} qos_prefix={qos_mod.PREFIX_IMPL} "
            f"host_path={eng.host_path}")
    if kind == "engine":
        return f"{head} ring={ring} loop=engine"
    sched = c["scheduler"]
    return (f"{head} express_loop={sched.express_loop} "
            f"aot_ready={sched._aot_ready} ring={ring}")
