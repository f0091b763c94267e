"""The edge stage on `bng run`'s normal path (`bng run --edge-enabled`): the
bulk route bind against a bind a subscriber, the two tables' sizes taken
apart, the app that builds the stage, the lease hook, the sink on both
loops, the Tracer's counters and its `mirror` lap, the blockers, and what
the flag leaves alone when it is off.

The device kernels and the compilers have tests/test_edge.py; the cell has
tests/test_edge_cell_rehearsal.py. Seeded, tiny sizes, CPU.
"""

import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.kits.multiisp import Plain  # noqa: E402
from bng_tpu.cli import BNGApp, BNGConfig  # noqa: E402
from bng_tpu.control import dhcp_codec, packets  # noqa: E402
from bng_tpu.control.intercept import (DeliveryMethod, Warrant,  # noqa: E402
                                       WarrantStatus)
from bng_tpu.control.nat import NATManager  # noqa: E402
from bng_tpu.control.routing import (LinkState, RoutingManager,  # noqa: E402
                                     StubPlatform, Upstream)
from bng_tpu.edge import (EST_MIRRORED, EST_ROUTE_MISSES,  # noqa: E402
                          EST_ROUTE_REWRITES, EST_TAP_FILTERED, MAX_WARRANTS,
                          EdgeTables, RouteProgram)
from bng_tpu.runtime import hostpath  # noqa: E402
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables  # noqa: E402
from bng_tpu.runtime.ring import PyRing  # noqa: E402
from bng_tpu.runtime.tables import FastPathTables  # noqa: E402
from bng_tpu.telemetry import spans as tele  # noqa: E402
from bng_tpu.utils.net import ip_to_u32, u32_to_ip  # noqa: E402

T0 = 1_753_000_000
SERVER_MAC = bytes.fromhex("02aabbccdd01")
ROUTER_MAC = bytes.fromhex("02ee00000001")
CLIENT_MAC = bytes.fromhex("02c0ffee0001")
REMOTE = ip_to_u32("93.184.216.34")
GATEWAYS = {f"192.0.2.{i + 1}": bytes((0x02, 0xEE, 0, 0, 1, i))
            for i in range(4)}
CLASS_TABLES = {"business": (101, 102), "wholesale": (103, 104),
                "nobody": (999,)}


def upstreams(rman, prog, weights=(1, 1, 1, 1)):
    for i, (gw, mac) in enumerate(GATEWAYS.items()):
        rman.add_upstream(Upstream(name=f"isp{i}", gateway=gw, table=101 + i,
                                   weight=weights[i], state=LinkState.UP))
        prog.set_neighbor(gw, mac)


def program(route_nbuckets=2048, weights=(1, 1, 1, 1)):
    rman = RoutingManager(platform=StubPlatform())
    edge = EdgeTables(tap_nbuckets=64, route_nbuckets=route_nbuckets)
    prog = RouteProgram(edge, rman, class_tables=CLASS_TABLES)
    upstreams(rman, prog, weights)
    return edge, prog, rman


# -- the bulk bind ----------------------------------------------------------

@pytest.mark.parametrize("seed,weights", [(11, (1, 1, 1, 1)),
                                          (2**31 + 12, (3, 1, 2, 1))])
def test_a_bulk_bind_writes_the_rows_of_a_bind_a_subscriber(seed, weights):
    """Same rows, bit for bit, same bindings, same counts: over four
    classes (one with nothing eligible), equal and unequal weights."""
    rng = np.random.default_rng(seed)
    n = 3000
    ips = rng.choice(1 << 20, n, replace=False).astype(np.uint32) + (10 << 24)
    names = np.asarray(["residential", "business", "wholesale", "nobody"],
                       dtype=object)[rng.choice(4, n, p=[.8, .1, .08, .02])]
    one_e, one, _ = program(weights=weights)
    for ip, klass in zip(ips.tolist(), names.tolist()):
        one.bind_subscriber(ip, klass)
    bulk_e, bulk, _ = program(weights=weights)
    routed = bulk.bulk_bind(ips, names.tolist())
    a, b = one_e.route_rows(), bulk_e.route_rows()
    assert routed == len(a) == len(b) == n - int((names == "nobody").sum())
    assert [k for k, _ in a] == [k for k, _ in b]
    assert all((x == y).all() for (_, x), (_, y) in zip(a, b))
    assert one._bindings == bulk._bindings and one.stats == bulk.stats
    assert bulk_e.route._dirty_all  # the next upload is a whole one
    # and both are what the plain reference elects
    plain = Plain([(f"isp{i}", 101 + i, weights[i], mac)
                   for i, mac in enumerate(GATEWAYS.values())],
                  CLASS_TABLES, {})
    for ip, klass in list(zip(ips.tolist(), names.tolist()))[:400]:
        want = plain.next_hop(ip, klass)
        row = bulk_e.get_route(ip)
        got = None if row is None else (int(row[1]).to_bytes(2, "big")
                                        + int(row[2]).to_bytes(4, "big"))
        assert got == want


def test_a_bulk_bind_with_one_class_for_all_and_after_a_flap():
    edge, prog, rman = program()
    ips = (np.arange(500) + ip_to_u32("10.16.0.0")).astype(np.uint32)
    assert prog.bulk_bind(ips) == 500  # residential
    before = dict(edge.route_rows())
    rman.get_upstream("isp1").state = LinkState.DOWN
    moved = prog.on_upstream_down("isp1")["rewritten"]
    plain = Plain([(f"isp{i}", 101 + i, 1, mac)
                   for i, mac in enumerate(GATEWAYS.values())], {}, {},
                  down=("isp1",))
    changed = 0
    for ip, row in edge.route_rows():
        mac = int(row[1]).to_bytes(2, "big") + int(row[2]).to_bytes(4, "big")
        assert mac == plain.next_hop(ip, "residential")
        changed += not (row == before[ip]).all()
    # modulo election: h % 4 against h % 3 keeps one subscriber in four
    assert changed == moved and 0.65 * 500 < moved < 0.85 * 500


def test_a_bulk_bind_takes_each_subscriber_once_and_none_that_is_bound():
    edge, prog, _ = program()
    prog.bind_subscriber("10.16.0.9", "business")
    with pytest.raises(ValueError, match="not bound yet"):
        prog.bulk_bind([ip_to_u32("10.16.0.9")])
    with pytest.raises(ValueError, match="each once"):
        prog.bulk_bind([5, 6, 5])
    with pytest.raises(ValueError, match="2 classes for 3"):
        prog.bulk_bind([5, 6, 7], ["business", "wholesale"])
    assert edge.route.count == 1 and prog.stats["bound"] == 1


def test_a_million_route_rows_beside_a_tap_table_for_warrants():
    """Sized as `bng run --edge-enabled --max-subscribers 1000000` sizes
    them: the route table takes a row a subscriber through the bulk bind,
    the stash all but untouched, and the tap table stays the warrants'."""
    from bng_tpu.ops.table import WAYS, nbuckets_for

    n = 1_000_000
    edge = EdgeTables(route_nbuckets=nbuckets_for(n),
                      tap_nbuckets=nbuckets_for(4096))
    rman = RoutingManager(platform=StubPlatform())
    prog = RouteProgram(edge, rman, class_tables=CLASS_TABLES)
    upstreams(rman, prog)
    ips = (np.arange(n) + ip_to_u32("10.16.0.0")).astype(np.uint32)
    names = np.asarray(["residential", "business", "wholesale"], dtype=object)[
        np.random.default_rng(5).choice(3, n, p=[.9, .08, .02])].tolist()
    assert prog.bulk_bind(ips, names) == n
    assert edge.route.count == n and edge.route.nbuckets == 524_288
    assert int(edge.route.used[edge.route.nbuckets * WAYS:].sum()) <= 8
    assert edge.tap.nbuckets == 4096 and edge.tap_geom != edge.route_geom
    assert edge.route.vals.nbytes + edge.route.keys.nbytes < 100 << 20
    at = np.random.default_rng(6).integers(0, n, 2000)
    for i in at.tolist():
        assert prog.expected_row(int(ips[i])) == tuple(
            int(x) for x in edge.get_route(int(ips[i]))[1:5])


# -- two geometries ---------------------------------------------------------

def make_engine(edge):
    fastpath = FastPathTables(sub_nbuckets=64, vlan_nbuckets=32,
                              cid_nbuckets=32, max_pools=4)
    fastpath.set_server_config(SERVER_MAC, ip_to_u32("10.0.0.1"))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=64, sub_nat_nbuckets=32)
    got = []
    eng = Engine(fastpath, nat, QoSTables(nbuckets=64),
                 AntispoofTables(nbuckets=64), edge=edge,
                 mirror_sink=lambda lane, frame, wid: got.append(
                     (lane, frame, wid)),
                 batch_size=16, pkt_slot=256, clock=lambda: float(T0))
    return eng, got


def fill_edge(edge):
    """Eight routed subscribers, two of them tapped (one filtered)."""
    rman = RoutingManager(platform=StubPlatform())
    prog = RouteProgram(edge, rman)
    upstreams(rman, prog)
    ips = [ip_to_u32("10.16.0.1") + i for i in range(8)]
    prog.bulk_bind(ips)
    edge.arm_tap(ips[1], 7)
    edge.arm_tap(ips[2], 9, [(443, 17, 0)])
    return ips


def window(ips):
    frames = [packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000,
                                 443 if i % 2 else 80, b"x" * 12 + bytes([i]))
              for i, ip in enumerate(ips)]
    frames.append(packets.udp_packet(CLIENT_MAC, SERVER_MAC,
                                     ip_to_u32("10.99.0.1"), REMOTE, 1, 2,
                                     b"unrouted"))
    return frames


def test_split_geometries_serve_what_the_shared_one_serves():
    """Same verdicts, same bytes, same mirror words, same counts, whether
    the two tables share a size (as before this PR) or not."""
    out = []
    for kw in ({"tap_nbuckets": 64, "route_nbuckets": 64},
               {"tap_nbuckets": 32, "route_nbuckets": 256}):
        edge = EdgeTables(**kw)
        ips = fill_edge(edge)
        eng, sunk = make_engine(edge)
        assert eng.geom.tap == edge.tap_geom and eng.geom.route == edge.route_geom
        res = eng.process(window(ips), from_access=True, now=float(T0))
        out.append((res["fwd"], res["slow"], res["dropped"], sunk,
                    eng.stats.edge.tolist()))
    assert out[0] == out[1]
    fwd, _slow, _dropped, sunk, counts = out[1]
    assert len(fwd) == 8  # routed lanes forward, NAT or no NAT
    assert [(lane, wid) for lane, _f, wid in sunk] == [(1, 7)]  # ips[2]: port 80
    assert counts[EST_MIRRORED] == 1 and counts[EST_TAP_FILTERED] == 1
    assert counts[EST_ROUTE_REWRITES] == 8 and counts[EST_ROUTE_MISSES] == 1
    assert {f[:6] for _lane, f in fwd} <= set(GATEWAYS.values())


def test_the_two_sizes_are_taken_apart_and_no_one_size_sizes_both():
    both = EdgeTables(stash=8)  # each table's own default
    assert both.tap_geom == both.route_geom
    assert both.tap.nbuckets == both.route.nbuckets == 1024
    with pytest.raises(TypeError, match="nbuckets"):
        EdgeTables(nbuckets=128)
    apart = EdgeTables(tap_nbuckets=128, route_nbuckets=1024)
    assert (apart.tap.nbuckets, apart.route.nbuckets) == (128, 1024)
    assert apart.tap_geom.nbuckets == 128 and apart.route_geom.nbuckets == 1024
    assert not hasattr(apart, "geom")  # nothing hands one size to both


def test_a_checkpoint_holds_the_two_tables_at_their_own_sizes():
    a = EdgeTables(tap_nbuckets=32, route_nbuckets=256)
    fill_edge(a)
    meta, arrays = a.checkpoint_state()
    assert meta["geom"]["tap"]["nbuckets"] == 32
    assert meta["geom"]["route"]["nbuckets"] == 256
    b = EdgeTables(tap_nbuckets=32, route_nbuckets=256)
    assert b.restore_state(meta, arrays) == {"tap": 2, "route": 8}
    assert [(k, v.tolist()) for k, v in a.route_rows()] == [
        (k, v.tolist()) for k, v in b.route_rows()]
    with pytest.raises(ValueError, match="geometry"):
        EdgeTables(tap_nbuckets=32, route_nbuckets=32).restore_state(
            meta, arrays)


# -- the app `bng run --edge-enabled` builds --------------------------------

class App:
    """`bng run --edge-enabled` at a tiny size; frames in and out by the
    ring, a clock the test moves."""

    def __init__(self, **flags):
        self.now = float(T0)
        cfg = BNGConfig(edge_enabled=True, slaac_enabled=False,
                        dhcpv6_enabled=False, walled_garden_enabled=False,
                        metrics_enabled=False, batch_size=8, lease_time=600,
                        **flags)
        self.app = BNGApp(cfg, clock=lambda: self.now)
        self.ring = self.app.components["ring"] = PyRing(
            nframes=128, frame_size=2048, depth=32)
        self.c = self.app.components
        self.xid = 0x300
        self.sunk = []
        sink = self

        class Keep:
            def deliver_cc(self, rec):
                sink.sunk.append((rec.warrant_id, rec.payload))

            def deliver_iri(self, rec):
                pass

        self.c["intercept"].add_exporter(DeliveryMethod.ETSI, Keep())
        upstreams(self.c["routing"], self.c["route_program"])

    def offer(self, frame, from_access=True):
        assert self.ring.rx_push(frame, from_access=from_access)
        for _ in range(3):  # the pipelined loop retires a beat later
            self.app.drive_once()
        tx, fwd = [], []
        while (got := self.ring.tx_pop()) is not None:
            tx.append(got[0])
        while (got := self.ring.fwd_pop()) is not None:
            fwd.append(got[0])
        return tx, fwd

    def dhcp(self, msg, requested=0):
        """One client message to the host's server; the decoded reply."""
        self.xid += 1
        p = dhcp_codec.build_request(
            CLIENT_MAC, msg, xid=self.xid, requested_ip=requested,
            server_id=ip_to_u32(self.app.config.server_ip) if requested else 0)
        frame = packets.udp_packet(CLIENT_MAC, b"\xff" * 6, 0, 0xFFFFFFFF, 68,
                                   67, p.encode().ljust(320, b"\x00"))
        reply = self.c["dhcp"].handle_frame(frame)
        return reply and dhcp_codec.decode(packets.decode(reply).payload)

    def warrant(self, ip, wid="w-1", **filters):
        self.c["intercept"].add_warrant(Warrant(
            id=wid, liid="LI-" + wid, status=WarrantStatus.ACTIVE,
            target_ipv4=u32_to_ip(ip), valid_from=self.now - 1,
            valid_until=self.now + 3600, **filters))
        return self.c["tap_program"].sync()


@pytest.fixture()
def app():
    a = App()
    yield a
    a.app.close()


def test_the_app_builds_the_stage_with_two_sizes(app):
    from bng_tpu.ops.table import nbuckets_for

    c = app.c
    eng = c["engine"]
    assert eng.edge is c["edge_tables"] and eng.mirror_sink is c["mirror_pump"]
    assert c["route_program"].manager is c["routing"]
    assert c["routing"].on_upstream_down == c["route_program"].on_upstream_down
    assert c["tap_program"].manager is c["intercept"]
    assert eng.tables.tap is not None and eng.tables.route is not None
    assert c["edge_tables"].tap.nbuckets == nbuckets_for(MAX_WARRANTS)
    assert not hasattr(BNGConfig(), "edge_max_warrants")  # a constant
    sized = BNGApp(BNGConfig(edge_enabled=True, max_subscribers=100_000,
                             slaac_enabled=False,
                             dhcpv6_enabled=False, metrics_enabled=False,
                             walled_garden_enabled=False, batch_size=8))
    try:
        e = sized.components["edge_tables"]
        assert e.route.nbuckets == nbuckets_for(100_000) == 65_536
        assert e.tap.nbuckets == nbuckets_for(MAX_WARRANTS) == 4096
        g = sized.components["engine"].geom
        assert (g.tap.nbuckets, g.route.nbuckets) == (4096, 65_536)
    finally:
        sized.close()


def test_a_committed_lease_is_bound_to_its_classes_next_hop(app):
    offer = app.dhcp(dhcp_codec.DISCOVER)
    ack = app.dhcp(dhcp_codec.REQUEST, requested=offer.yiaddr)
    assert ack.msg_type == dhcp_codec.ACK
    ip = ack.yiaddr
    row = app.c["edge_tables"].get_route(ip)
    assert row is not None
    plain = Plain([(f"isp{i}", 101 + i, 1, mac)
                   for i, mac in enumerate(GATEWAYS.values())], {}, {})
    want = plain.next_hop(ip, "residential")
    assert int(row[1]).to_bytes(2, "big") + int(row[2]).to_bytes(4, "big") == want
    # its upstream data leaves for that gateway: a routed lane forwards
    # whether NAT translates it or not (no public pool here)
    up = packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000, 443,
                            b"up-" + bytes(8))
    fwd = []
    for _ in range(2):
        fwd = app.offer(up)[1] or fwd
    assert len(fwd) == 1 and fwd[0][:6] == want and fwd[0][6:12] == up[6:12]
    assert packets.decode(fwd[0]).payload == packets.decode(up).payload
    # release: the row goes with the lease
    app.xid += 1
    p = dhcp_codec.build_request(CLIENT_MAC, dhcp_codec.RELEASE, xid=app.xid,
                                 server_id=ip_to_u32(app.app.config.server_ip))
    p.ciaddr = ip
    app.c["dhcp"].handle_frame(packets.udp_packet(
        CLIENT_MAC, SERVER_MAC, ip, ip_to_u32(app.app.config.server_ip), 68,
        67, p.encode().ljust(320, b"\x00")))
    assert app.c["edge_tables"].get_route(ip) is None
    assert ip not in app.c["route_program"]._bindings


def test_a_tapped_subscribers_frames_reach_the_sink_as_they_arrived(app):
    ip = ip_to_u32("10.0.5.5")
    app.c["route_program"].bind_subscriber(ip, "business")
    assert app.warrant(ip)["armed"] == 1
    up = packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000, 443,
                            b"tapped-" + bytes(8))
    with tele.armed() as tr:
        fwd = []
        for _ in range(2):
            fwd = app.offer(up)[1] or fwd
        sums = tr.sums()
    assert len(fwd) == 1 and fwd[0] != up  # left rewritten
    assert app.sunk == [("w-1", up), ("w-1", up)]  # as it arrived, both times
    assert app.c["mirror_pump"].stats["cc_records"] == 2
    assert (sums["edge_mirrored"], sums["edge_filtered"]) == (2, 0)
    assert sums["edge_rewrites"] == 2 and sums["edge_route_miss"] == 0
    assert sums["stage_ns"]["mirror"] > 0
    assert sums["stage_ns"]["mirror"] <= sums["stage_ns"]["reply"]
    # every read of a retire was started at its dispatch, the mirror column's
    # too: none blocks
    assert sums["xfer"]["fetch_calls"] == 0
    block = app.app.stats()["edge"]
    assert block["routes"] == 1 and block["taps"] == 1
    assert block["device"] == {"mirrored": 2, "filtered": 0, "rewrites": 2,
                               "route_miss": 0}
    assert block["sink"]["cc_records"] == 2


def test_a_warrant_past_its_window_leaves_the_device_at_the_next_sweep(app):
    ip = ip_to_u32("10.0.5.6")
    app.warrant(ip, "w-old")
    assert app.c["edge_tables"].get_tap(ip) is not None
    app.now += 3601
    app.app._last_expire = -1e18
    app.app.tick()
    assert app.c["edge_tables"].get_tap(ip) is None
    assert app.c["intercept"].get_warrant("w-old").status == WarrantStatus.EXPIRED


def test_the_schedulers_loop_hands_mirrored_frames_to_the_sink():
    a = App(scheduler_enabled=True, sched_express_batch=8,
            sched_express_max_wait_us=0.0)
    try:
        assert hasattr(a.ring, "rx_pop") and "scheduler" in a.c
        ip = ip_to_u32("10.0.5.7")
        a.c["route_program"].bind_subscriber(ip)
        a.warrant(ip, "w-s", filter_protocols=[17], filter_dest_ports=[443])
        hit = packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000,
                                 443, b"sched-" + bytes(8))
        miss = packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000,
                                  80, b"sched-" + bytes(8))
        with tele.armed() as tr:
            for frame in (hit, miss, hit):
                assert a.ring.rx_push(frame, from_access=True)
            for beat in range(4000):  # the bulk lane closes on its deadline
                a.app.drive_once()
                if len(a.sunk) == 2 and not len(a.c["scheduler"]._bulk_ring):
                    break
                a.now += 0.01  # the clock the lanes' deadlines read
                if beat >= 400:
                    # the lane retires a step when it is ready and never
                    # waits for one: on a loaded CPU give the step time
                    time.sleep(0.005)
            sums = tr.sums()
        assert a.sunk == [("w-s", hit), ("w-s", hit)]
        assert (sums["edge_mirrored"], sums["edge_filtered"]) == (2, 1)
        assert sums["stage_ns"]["mirror"] > 0
        snap = a.c["scheduler"].stats_snapshot()["trace"]
        assert snap["edge_mirrored"] == 2 and snap["edge_rewrites"] == 3
    finally:
        a.app.close()


def test_no_program_is_built_once_the_loop_serves(app):
    """The stage goes through the step ladder: the first beat builds every
    rung, and windows after it find their program."""
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: built.append(name)
        if name.endswith("backend_compile_duration") else None)
    ip = ip_to_u32("10.0.5.8")
    app.c["route_program"].bind_subscriber(ip)
    app.warrant(ip, "w-r")
    app.app.drive_once()  # builds the ladder
    assert app.app._rungs_built_for is app.c["engine"]
    n0 = len(built)
    up = packets.udp_packet(CLIENT_MAC, SERVER_MAC, ip, REMOTE, 40000, 443,
                            b"rung-" + bytes(8))
    for _ in range(3):
        app.offer(up)
    assert len(built) == n0 and len(app.sunk) == 3


@pytest.mark.parametrize("flags,where", [
    ({"shards": 2}, "sharded_blockers"),
    ({"slowpath_workers": 2, "slowpath_worker_mode": "inline"},
     "fleet_blockers")])
def test_the_flag_is_a_named_blocker_where_the_stage_is_not_wired(flags, where):
    cfg = BNGConfig(edge_enabled=True, slaac_enabled=False,
                    dhcpv6_enabled=False, walled_garden_enabled=False,
                    metrics_enabled=False, batch_size=8, shard_nbuckets=64,
                    **flags)
    a = BNGApp(cfg)
    try:
        assert "edge" in getattr(a, where)
        if where == "fleet_blockers":
            assert "fleet" not in a.components  # collapsed, and said so
        else:
            assert "edge_tables" not in a.components
    finally:
        a.close()


def test_beside_a_blocked_fleet_a_committed_lease_still_gets_its_route_row():
    """`--edge-enabled --slowpath-workers 2`: the fleet collapses to the
    in-process server, which commits every lease, so the hook that binds a
    lease to its next hop has to be on it."""
    app = App(slowpath_workers=2, slowpath_worker_mode="inline")
    try:
        assert app.app.fleet_blockers == ["edge"] and "fleet" not in app.c
        offer = app.dhcp(dhcp_codec.DISCOVER)
        ack = app.dhcp(dhcp_codec.REQUEST, requested=offer.yiaddr)
        assert ack.msg_type == dhcp_codec.ACK
        row = app.c["edge_tables"].get_route(ack.yiaddr)
        assert row is not None and ack.yiaddr in app.c["route_program"]._bindings
        assert (int(row[1]).to_bytes(2, "big") + int(row[2]).to_bytes(4, "big")
                in set(GATEWAYS.values()))
    finally:
        app.app.close()


# -- the flag off -------------------------------------------------------------

def lowered(eng) -> str:
    b = eng.B
    return eng._step.lower(
        eng.tables, np.zeros((hostpath.window_rows(b, eng.L), eng.L), np.uint8),
        np.uint32(T0), np.uint32(0)).as_text()


def test_without_the_flag_the_lowered_step_is_the_one_it_was():
    """`bng run` without `--edge-enabled` lowers the text an engine built
    as before this PR lowers (no `edge=`, no sink): no table, no scope, no
    output of the stage; with the flag the stage is in the text."""
    kw = dict(slaac_enabled=False, dhcpv6_enabled=False, metrics_enabled=False,
              walled_garden_enabled=False, batch_size=8)
    off = BNGApp(BNGConfig(**kw))
    on = BNGApp(BNGConfig(edge_enabled=True, **kw))
    try:
        assert BNGConfig().edge_enabled is False
        c = off.components
        assert "edge_tables" not in c and "mirror_pump" not in c
        eng = c["engine"]
        assert eng.edge is None and eng.mirror_sink is None
        assert eng.tables.tap is None and eng.tables.route is None
        assert eng.geom.tap is None and eng.geom.route is None
        assert "edge" not in off.stats()
        plain = Engine(fastpath=c["fastpath"], nat=c["nat"], qos=c["qos"],
                       antispoof=c["antispoof"], batch_size=8,
                       clock=off.clock)
        assert plain.geom == eng.geom
        text = lowered(eng)
        assert text == lowered(plain)
        with_stage = lowered(on.components["engine"])
        assert with_stage != text and len(with_stage) > len(text)
    finally:
        off.close()
        on.close()


def test_the_tracers_counters_read_zero_where_the_stage_is_off():
    zero = tele.Tracer().sums()
    for key in ("edge_mirrored", "edge_filtered", "edge_rewrites",
                "edge_route_miss"):
        assert zero[key] == 0 and tele.trace_sums()[key] >= 0
    assert zero["stage_ns"]["mirror"] == 0 and zero["starved_ns"]["mirror"] == 0
    assert tele.STAGE_NAMES[tele.MIRROR] == "mirror"
    assert tele.STAGE_NAMES[-1] == "total"
    tele.edge_lanes(1, 2, 3, 4)  # disarmed: nothing moves
    with tele.armed() as tr:
        tele.edge_lanes(1, 2, 3, 4)
        tele.edge_lanes(1, 0, 0, 1)
        got = tr.sums()
    assert [got[k] for k in ("edge_mirrored", "edge_filtered", "edge_rewrites",
                             "edge_route_miss")] == [2, 2, 3, 5]
