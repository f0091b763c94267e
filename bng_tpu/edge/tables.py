"""Host authority for the edge-protection device tables (ISSUE 17).

`EdgeTables` is the single writer for the tap-match and next-hop route
tables, in the `runtime/tables.py` mold: numpy host mirrors of the
device cuckoo tables plus dense side arrays, draining bounded
`TableUpdate` batches through the engine's existing update tail. The
compile layer (`edge/compile.py`) translates `control/intercept.py`
warrants and `control/routing.py` manager state into row mutations
here; nothing else writes (bngcheck single-writer allowlist).
"""

from __future__ import annotations

import numpy as np

from bng_tpu.edge.ops import (
    ROUTE_WORDS,
    RW_CLASS,
    RW_FLAG,
    RW_MAC_HI,
    RW_MAC_LO,
    RW_TABLE,
    TAP_CONFIG_WORDS,
    TAP_FILTER_COLS,
    TAP_WORDS,
    TC_ARMED,
    TF_PEER,
    TF_PORT,
    TF_PROTO,
    TF_WID,
    TW_FLAG,
    TW_WID,
)
from bng_tpu.ops.table import HostTable, TableGeom, placed

MAX_TAP_FILTERS = 64
# the warrants `bng run --edge-enabled` sizes its tap table for
MAX_WARRANTS = 4096


class EdgeTables:
    """Host side of the device tap-match + route tables.

    Both tables key on the subscriber IPv4 (one uint32 word). The tap
    table's dense companions — `tap_filters[F, 4]` rows and the
    `tap_config` armed predicate — ride every update batch wholesale
    (they are tiny), exactly like FastPathTables' pools/server arrays.
    """

    def __init__(self, tap_nbuckets: int = 1 << 10,
                 route_nbuckets: int = 1 << 10, stash: int = 64,
                 update_slots: int = 64,
                 max_filters: int = MAX_TAP_FILTERS):
        # the two tables are sized apart: a tap row a warrant, a route row
        # a subscriber
        self.tap = HostTable(tap_nbuckets, key_words=1, val_words=TAP_WORDS,
                             stash=stash, name="edge_tap")
        self.route = HostTable(route_nbuckets, key_words=1,
                               val_words=ROUTE_WORDS, stash=stash,
                               name="edge_route")
        self.tap_filters = np.zeros((max_filters, TAP_FILTER_COLS),
                                    dtype=np.uint32)
        self.tap_config = np.zeros((TAP_CONFIG_WORDS,), dtype=np.uint32)
        self.tap_geom = TableGeom(tap_nbuckets, stash)
        self.route_geom = TableGeom(route_nbuckets, stash)
        self.update_slots = update_slots
        self._armed = 0  # live tap rows (the TC_ARMED predicate source)

    # -- tap CRUD (writer: edge/compile.py InterceptTapProgram) ---------
    def arm_tap(self, subscriber_ip: int, wid: int,
                filters: list[tuple[int, int, int]] | tuple = ()) -> None:
        """Arm a tap row for `subscriber_ip` under warrant id `wid`.
        `filters` is a list of (port, proto, peer_ip) conjunct rows
        (0 = wildcard column); the lane mirrors if ANY row matches.
        Re-arming the same IP replaces the row (upsert)."""
        if wid <= 0:
            raise ValueError("warrant id must be positive (0 = free row)")
        prior = self.tap.lookup([subscriber_ip])
        row = np.zeros((TAP_WORDS,), dtype=np.uint32)
        row[TW_FLAG] = 1
        row[TW_WID] = wid
        self.tap.insert([subscriber_ip], row)
        if prior is None:
            self._armed += 1
        self.set_tap_filters(wid, filters)
        self.tap_config[TC_ARMED] = self._armed

    def disarm_tap(self, subscriber_ip: int) -> bool:
        """Remove the tap row for `subscriber_ip`. The wid's filter rows
        stay until the compiler clears them (another IP may share the
        warrant); orphaned filter rows are harmless — no row carries
        their wid."""
        ok = self.tap.delete([subscriber_ip])
        if ok:
            self._armed -= 1
            self.tap_config[TC_ARMED] = self._armed
        return ok

    def get_tap(self, subscriber_ip: int):
        return self.tap.lookup([subscriber_ip])

    def set_tap_filters(self, wid: int,
                        filters: list[tuple[int, int, int]] | tuple) -> int:
        """Replace warrant `wid`'s dense filter rows; returns rows
        written (silently truncates at the dense array capacity — the
        compiler logs the drop)."""
        fw = self.tap_filters[:, TF_WID]
        rows = self.tap_filters[(fw != 0) & (fw != np.uint32(wid))]
        self.tap_filters[:] = 0
        self.tap_filters[:len(rows)] = rows
        free = len(self.tap_filters) - len(rows)
        wrote = 0
        for port, proto, peer in tuple(filters)[:free]:
            r = self.tap_filters[len(rows) + wrote]
            r[TF_WID] = wid
            r[TF_PORT] = port
            r[TF_PROTO] = proto
            r[TF_PEER] = peer
            wrote += 1
        return wrote

    # -- route CRUD (writer: edge/compile.py RouteProgram) --------------
    def set_route(self, subscriber_ip: int, nh_mac: bytes, table_id: int,
                  klass: int = 0) -> None:
        """Install/replace the next-hop row for `subscriber_ip`:
        gateway MAC + ISP table id + the class code the ECMP selection
        was made under."""
        row = np.zeros((ROUTE_WORDS,), dtype=np.uint32)
        row[RW_FLAG] = 1
        row[RW_MAC_HI] = int.from_bytes(nh_mac[:2], "big")
        row[RW_MAC_LO] = int.from_bytes(nh_mac[2:6], "big")
        row[RW_TABLE] = table_id
        row[RW_CLASS] = klass
        self.route.insert([subscriber_ip], row)

    def bulk_set_routes(self, subscriber_ips, mac_hi, mac_lo, table_ids,
                        klasses) -> None:
        """`set_route` for subscribers that hold no row yet, at the
        1M-subscriber scale: the same row words, one vectorized build. As
        after any bulk build, the next upload is a whole one
        (`Engine.resync_tables`)."""
        ips = np.asarray(subscriber_ips, dtype=np.uint32)
        rows = np.zeros((len(ips), ROUTE_WORDS), dtype=np.uint32)
        rows[:, RW_FLAG] = 1
        rows[:, RW_MAC_HI] = mac_hi
        rows[:, RW_MAC_LO] = mac_lo
        rows[:, RW_TABLE] = table_ids
        rows[:, RW_CLASS] = klasses
        self.route.bulk_insert(ips[:, None], rows)

    def clear_route(self, subscriber_ip: int) -> bool:
        return self.route.delete([subscriber_ip])

    def get_route(self, subscriber_ip: int):
        return self.route.lookup([subscriber_ip])

    # -- row iteration (audit surface) ----------------------------------
    def tap_rows(self) -> list[tuple[int, np.ndarray]]:
        """[(subscriber_ip, row)] for every live tap row."""
        return self._rows(self.tap)

    def route_rows(self) -> list[tuple[int, np.ndarray]]:
        return self._rows(self.route)

    @staticmethod
    def _rows(table: HostTable) -> list[tuple[int, np.ndarray]]:
        out = [(int(table.keys[s, 0]), table.vals[s].copy())
               for s in np.nonzero(table.used)[0]]
        out.sort(key=lambda kv: kv[0])
        return out

    # -- device sync ----------------------------------------------------
    def make_updates(self):
        """(tap delta, filters, config, route delta) — the edge tail of
        the engine's per-step update batch."""
        return (self.tap.make_update(self.update_slots),
                placed(self, "tap_filters", self.tap_filters),
                placed(self, "tap_config", self.tap_config),
                self.route.make_update(self.update_slots))

    def empty_updates(self):
        """No-op deltas that do not consume dirty tracking (scheduler
        bulk lane); the dense arrays are compared with what was last
        placed on every call — they apply wholesale."""
        return (self.tap.empty_update(self.update_slots),
                placed(self, "tap_filters", self.tap_filters),
                placed(self, "tap_config", self.tap_config),
                self.route.empty_update(self.update_slots))

    def dirty_count(self) -> int:
        return self.tap.dirty_count() + self.route.dirty_count()

    # -- checkpoint/warm-restart (runtime/checkpoint.py) ----------------
    def checkpoint_state(self) -> tuple[dict, dict]:
        meta = {"geom": {"tap": self.tap.checkpoint_geom(),
                         "route": self.route.checkpoint_geom()},
                "max_filters": len(self.tap_filters)}
        arrays = {f"{t}.{k}": v
                  for t in ("tap", "route")
                  for k, v in getattr(self, t).checkpoint_arrays().items()}
        arrays["tap_filters"] = self.tap_filters
        arrays["tap_config"] = self.tap_config
        return meta, arrays

    def restore_state(self, meta: dict, arrays: dict) -> dict[str, int]:
        rows = {}
        for t in ("tap", "route"):
            rows[t] = getattr(self, t).restore_arrays(
                {k: arrays[f"{t}.{k}"] for k in ("keys", "vals", "used")},
                meta["geom"][t])
        if arrays["tap_filters"].shape != self.tap_filters.shape:
            raise ValueError(
                f"checkpoint tap_filters shape "
                f"{arrays['tap_filters'].shape} != {self.tap_filters.shape}")
        self.tap_filters[:] = arrays["tap_filters"]
        self.tap_config[:] = arrays["tap_config"]
        self._armed = rows["tap"]
        self.tap_config[TC_ARMED] = self._armed
        return rows
