"""AOT express OFFER path (ISSUE 13).

The acceptance surface of the minimal-program express lane:

- **Byte identity vs the full program**: the whole express path
  (admission descriptor -> AOT probe program -> host template patch-in)
  produces replies bit-identical to `_dhcp_jit`'s on-device compose,
  across >=4 table geometries, over the full addressing matrix
  (broadcast/unicast/relayed, VLAN/QinQ, option-82, DISCOVER/REQUEST,
  dns variants, expired/unknown -> slow).
- **Byte identity vs the codec**: an express template reply equals the
  slow-path server's codec-built reply for the same request (the
  express retire path routes through ReplyTemplate patch-in
  unconditionally); storms of several batches and a partial one decode,
  by the codec, to the bindings the tables were given.
- **AOT cache discipline**: a geometry hit serves without retracing
  (ops/express.TRACE_COUNT is a trace-time counter); a geometry miss
  falls back to the jit-full path LOUDLY (miss counter + flight-record
  trigger + ring-meta program identity), never silently.
- **SLO wiring**: the `device` stage budget (the paper's 50us) verdicts
  over express-fed breakdowns.

Geometries are kept tiny: the express program is small, but each
geometry of the identity matrix also compiles the full `_dhcp_jit`
comparison program.
"""

from __future__ import annotations

import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.dhcp_server import DHCPServer
from bng_tpu.control.metrics import BNGMetrics
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.ops import express as ex
from bng_tpu.runtime.engine import Engine
from bng_tpu.runtime.scheduler import SchedulerConfig, TieredScheduler
from bng_tpu.runtime.tables import FastPathTables
from bng_tpu.telemetry import FlightRecorder, RecorderConfig
from bng_tpu.telemetry import slo
from bng_tpu.telemetry import spans as tele
from bng_tpu.telemetry.recorder import TRIG_EXPRESS_AOT_MISS
from bng_tpu.utils.net import ip_to_u32, parse_mac

pytestmark = pytest.mark.express

SERVER_MAC = parse_mac("02:aa:bb:cc:dd:01")
SERVER_IP = ip_to_u32("10.0.0.1")
NOW = 1_700_000_000


class FakeClock:
    def __init__(self, t=float(NOW)):
        self.t = t

    def __call__(self):
        return self.t


def mac_of(i: int) -> bytes:
    return (0x02B0 << 32 | i).to_bytes(6, "big")


def build_fp(sub_nb=256, vlan_nb=64, cid_nb=64) -> FastPathTables:
    """Three pools (dns1+dns2 / dns1 only / no dns) + the subscriber
    matrix the addressing cases below probe."""
    fp = FastPathTables(sub_nbuckets=sub_nb, vlan_nbuckets=vlan_nb,
                        cid_nbuckets=cid_nb, max_pools=8)
    fp.set_server_config(SERVER_MAC, SERVER_IP)
    fp.add_pool(1, ip_to_u32("10.0.0.0"), 24, SERVER_IP,
                ip_to_u32("8.8.8.8"), ip_to_u32("8.8.4.4"), 3600)
    fp.add_pool(2, ip_to_u32("10.1.0.0"), 16, ip_to_u32("10.1.0.1"),
                ip_to_u32("1.1.1.1"), 0, 7200)
    fp.add_pool(3, ip_to_u32("10.2.0.0"), 20, ip_to_u32("10.2.0.1"),
                0, 0, 600)
    fp.add_subscriber(mac_of(0), 1, ip_to_u32("10.0.0.50"), NOW + 600)
    fp.add_subscriber(mac_of(1), 2, ip_to_u32("10.1.0.60"), NOW + 600)
    fp.add_subscriber(mac_of(2), 3, ip_to_u32("10.2.0.70"), NOW + 600)
    fp.add_vlan_subscriber(100, 0, 1, ip_to_u32("10.0.0.80"), NOW + 600)
    fp.add_vlan_subscriber(200, 30, 2, ip_to_u32("10.1.0.90"), NOW + 600)
    fp.add_circuit_id_subscriber(b"port-7/0/1", 1, ip_to_u32("10.0.0.99"),
                                 NOW + 600)
    fp.add_subscriber(mac_of(9), 1, ip_to_u32("10.0.0.44"), NOW - 5)  # expired
    return fp


def dhcp_frame(mac, msg_type, vlans=None, giaddr=0, ciaddr=0,
               broadcast=False, circuit_id=b"", src_ip=0):
    pkt = dhcp_codec.build_request(mac, msg_type, giaddr=giaddr,
                                   ciaddr=ciaddr, broadcast=broadcast,
                                   circuit_id=circuit_id)
    if not circuit_id:
        pkt.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                            bytes([1, 3, 6, 15, 51, 54])))
    payload = pkt.encode().ljust(320, b"\x00")
    return packets.udp_packet(
        src_mac=mac, dst_mac=b"\xff" * 6, src_ip=src_ip,
        dst_ip=0xFFFFFFFF, src_port=68, dst_port=67, payload=payload,
        vlans=vlans)


def case_frames() -> list[bytes]:
    """The addressing/resolution matrix, one frame per case (8 total)."""
    return [
        dhcp_frame(mac_of(0), dhcp_codec.DISCOVER),                 # bcast OFFER
        dhcp_frame(mac_of(1), dhcp_codec.REQUEST),                  # ACK, dns1-only
        dhcp_frame(mac_of(2), dhcp_codec.DISCOVER, broadcast=True),  # no-dns pool
        dhcp_frame(mac_of(3), dhcp_codec.DISCOVER, vlans=[100]),    # vlan tier
        dhcp_frame(mac_of(4), dhcp_codec.DISCOVER, vlans=[200, 30]),  # qinq tier
        dhcp_frame(mac_of(5), dhcp_codec.DISCOVER,
                   circuit_id=b"port-7/0/1"),                       # opt82 tier
        dhcp_frame(mac_of(0), dhcp_codec.REQUEST,
                   giaddr=ip_to_u32("10.9.9.9")),                   # relayed
        dhcp_frame(mac_of(0), dhcp_codec.REQUEST,
                   ciaddr=ip_to_u32("10.0.0.50"),
                   src_ip=ip_to_u32("10.0.0.50")),                  # L2 unicast renew
    ]


def build_sched(fp: FastPathTables, express_batch: int,
                express_aot: bool, clock=None) -> TieredScheduler:
    clock = clock or FakeClock()
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=64, sub_nat_nbuckets=64)
    eng = Engine(fp, nat, batch_size=32, pkt_slot=512, clock=clock)
    return TieredScheduler(eng, SchedulerConfig(
        express_batch=express_batch, bulk_batch=32,
        express_aot=express_aot), clock=clock)


def run_express(sched: TieredScheduler, frames: list[bytes]) -> dict:
    out = sched.process(frames)
    return {"tx": dict(out["tx"]), "slow": sorted(i for i, _ in out["slow"])}


# ---------------------------------------------------------------------------
# descriptor extraction (host admission parse)
# ---------------------------------------------------------------------------

class TestDescriptor:
    def test_plain_discover(self):
        d = ex.parse_express(dhcp_frame(mac_of(0), dhcp_codec.DISCOVER))
        assert d is not None
        w = d.words
        assert w[ex.XD_FLAGS] & ex.XF_VALID
        assert w[ex.XD_FLAGS] & ex.XF_BCAST  # ciaddr==0 -> broadcast
        assert not (w[ex.XD_FLAGS] & (ex.XF_VLAN | ex.XF_CID | ex.XF_RELAYED))
        assert w[ex.XD_MAC_HI] == 0x02B0 and w[ex.XD_MAC_LO] == 0
        assert d.msg_type == dhcp_codec.DISCOVER and not d.relayed

    def test_vlan_and_qinq_key(self):
        d1 = ex.parse_express(dhcp_frame(mac_of(0), dhcp_codec.DISCOVER,
                                         vlans=[100]))
        assert d1.vlan_off == 4 and d1.words[ex.XD_VLAN] == (100 << 16)
        d2 = ex.parse_express(dhcp_frame(mac_of(0), dhcp_codec.DISCOVER,
                                         vlans=[200, 30]))
        assert d2.vlan_off == 8
        assert d2.words[ex.XD_VLAN] == (200 << 16) | 30
        assert d2.words[ex.XD_FLAGS] & ex.XF_VLAN

    def test_circuit_id_words(self):
        from bng_tpu.runtime.tables import pack_cid_host

        d = ex.parse_express(dhcp_frame(mac_of(0), dhcp_codec.DISCOVER,
                                        circuit_id=b"port-7/0/1"))
        assert d.words[ex.XD_FLAGS] & ex.XF_CID
        np.testing.assert_array_equal(
            d.words[ex.XD_CID0: ex.XD_CID0 + 8],
            pack_cid_host(b"port-7/0/1"))

    def test_relayed_flags(self):
        d = ex.parse_express(dhcp_frame(mac_of(0), dhcp_codec.REQUEST,
                                        giaddr=ip_to_u32("10.9.9.9")))
        assert d.relayed and not d.use_bcast
        assert d.words[ex.XD_FLAGS] & ex.XF_RELAYED

    def test_ineligible_frames_are_none(self):
        # non-DHCP, short, and wrong-message-type frames never probe
        assert ex.parse_express(b"\x00" * 60) is None
        data = packets.udp_packet(mac_of(0), b"\xff" * 6, 0, 0xFFFFFFFF,
                                  68, 53, b"x" * 300)
        assert ex.parse_express(data) is None
        rel = dhcp_frame(mac_of(0), dhcp_codec.RELEASE)
        assert ex.parse_express(rel) is None


# ---------------------------------------------------------------------------
# byte identity: express path vs the full _dhcp_jit program
# ---------------------------------------------------------------------------

GEOMETRIES = [
    dict(sub_nb=256, vlan_nb=64, cid_nb=64, batch=8),
    dict(sub_nb=128, vlan_nb=32, cid_nb=32, batch=8),
    dict(sub_nb=512, vlan_nb=128, cid_nb=64, batch=16),
    dict(sub_nb=256, vlan_nb=64, cid_nb=128, batch=8),
]

class TestByteIdentity:
    # each geometry compiles the full _dhcp_jit comparison program
    @pytest.mark.parametrize("gi", range(len(GEOMETRIES)))
    def test_express_matches_dhcp_jit(self, gi):
        g = GEOMETRIES[gi]
        frames = case_frames()
        sched_aot = build_sched(build_fp(g["sub_nb"], g["vlan_nb"],
                                         g["cid_nb"]),
                                g["batch"], express_aot=True)
        out_aot = run_express(sched_aot, frames)
        sched_jit = build_sched(build_fp(g["sub_nb"], g["vlan_nb"],
                                         g["cid_nb"]),
                                g["batch"], express_aot=False)
        out_jit = run_express(sched_jit, frames)

        # every on-device answer present on both paths, byte-identical
        assert set(out_aot["tx"]) == set(out_jit["tx"])
        assert len(out_aot["tx"]) == 8  # every case resolves on device
        for lane, frame in out_aot["tx"].items():
            assert frame == out_jit["tx"][lane], f"lane {lane} differs"
        assert out_aot["slow"] == out_jit["slow"]
        snap = sched_aot.stats_snapshot()["express"]
        assert snap["aot_dispatches"] >= 1 and snap["aot_misses"] == 0

    def test_expired_and_unknown_go_slow_on_both_paths(self):
        frames = [dhcp_frame(mac_of(9), dhcp_codec.DISCOVER),  # expired
                  dhcp_frame(mac_of(77), dhcp_codec.DISCOVER)]  # unknown
        for aot in (True, False):
            out = run_express(build_sched(build_fp(), 8, aot), frames)
            assert out["tx"] == {} and out["slow"] == [0, 1]


# ---------------------------------------------------------------------------
# byte identity: express template reply vs the codec-built reply
# ---------------------------------------------------------------------------

class TestCodecIdentity:
    # the request shapes a client or a relay sends for one binding; the
    # first DISCOVER makes the lease, `shape` is the frame compared
    @pytest.mark.parametrize("shape", [
        dict(msg_type=dhcp_codec.DISCOVER),
        dict(msg_type=dhcp_codec.DISCOVER, broadcast=True),
        dict(msg_type=dhcp_codec.DISCOVER, giaddr=ip_to_u32("10.9.9.9")),
        dict(msg_type=dhcp_codec.REQUEST),
        dict(msg_type=dhcp_codec.REQUEST, broadcast=True),
        dict(msg_type=dhcp_codec.REQUEST, giaddr=ip_to_u32("10.9.9.9")),
    ], ids=["discover", "discover-bcast-flag", "discover-relayed",
            "request", "request-bcast-flag", "request-relayed"])
    def test_express_reply_matches_codec_built(self, shape):
        clock = FakeClock()
        fp = build_fp()
        pools = PoolManager(fp)
        pools.add_pool(Pool(pool_id=1, network=ip_to_u32("10.0.0.0"),
                            prefix_len=24, gateway=SERVER_IP,
                            dns_primary=ip_to_u32("8.8.8.8"),
                            dns_secondary=ip_to_u32("8.8.4.4"),
                            lease_time=3600))
        server = DHCPServer(SERVER_MAC, SERVER_IP, pools,
                            fastpath_tables=fp, clock=clock)
        mac = mac_of(40)
        offer = server.handle_frame(dhcp_frame(mac, dhcp_codec.DISCOVER))
        assert offer is not None
        yiaddr = dhcp_codec.decode(packets.decode(offer).payload).yiaddr
        frame = dhcp_frame(mac, **shape)
        codec_reply = server.handle_frame(frame)
        assert codec_reply is not None
        assert dhcp_codec.decode(
            packets.decode(codec_reply).payload).yiaddr == yiaddr
        # install the same binding on the fast path; the express reply
        # must be byte-identical to the server's template-rendered frame
        fp.add_subscriber(mac, 1, yiaddr, NOW + 3600)
        sched = build_sched(fp, 8, express_aot=True, clock=clock)
        out = run_express(sched, [frame])
        assert out["tx"][0] == codec_reply


# ---------------------------------------------------------------------------
# the AOT lane over storms: what the codec reads back from every reply
# ---------------------------------------------------------------------------

# per frame of case_frames(): the message type and yiaddr a reply must
# decode to, from the bindings build_fp() installs; pools 1-3 lease for
# 3600, 7200 and 600 s
CASE_REPLIES = [
    (dhcp_codec.OFFER, "10.0.0.50", 3600),
    (dhcp_codec.ACK, "10.1.0.60", 7200),
    (dhcp_codec.OFFER, "10.2.0.70", 600),
    (dhcp_codec.OFFER, "10.0.0.80", 3600),
    (dhcp_codec.OFFER, "10.1.0.90", 7200),
    (dhcp_codec.OFFER, "10.0.0.99", 3600),
    (dhcp_codec.ACK, "10.0.0.50", 3600),
    (dhcp_codec.ACK, "10.0.0.50", 3600),
]


def storm_frames(n: int) -> list[bytes]:
    """n frames cycling the case matrix: several express batches plus a
    partial one."""
    base = case_frames()
    return [base[i % len(base)] for i in range(n)]


def assert_codec_reads_bindings(out: dict, frames: list[bytes]) -> None:
    """Every frame answered on the device, and each reply decodes (by the
    codec, nothing the device computed) to its case's binding."""
    assert out["slow"] == [] and sorted(out["tx"]) == list(range(len(frames)))
    for i, frame in enumerate(frames):
        req = dhcp_codec.decode(packets.decode(frame).payload)
        d = packets.decode(out["tx"][i])
        rep = dhcp_codec.decode(d.payload)
        mtype, yiaddr, lease = CASE_REPLIES[i % len(CASE_REPLIES)]
        assert d.ip_checksum_ok, f"frame {i}"
        assert (rep.op, rep.msg_type, rep.yiaddr) == (
            2, mtype, ip_to_u32(yiaddr)), f"frame {i}"
        assert (rep.xid, rep.chaddr[:6]) == (req.xid, req.chaddr[:6])
        assert rep.server_id == SERVER_IP
        assert rep.opt(dhcp_codec.OPT_LEASE_TIME) == lease.to_bytes(4, "big")


class TestAotLaneStorms:
    # (express_batch, full batches, sub_nb, vlan_nb, cid_nb)
    @pytest.mark.parametrize("batch,k,sub_nb,vlan_nb,cid_nb", [
        (8, 4, 256, 64, 64), (8, 2, 128, 32, 32), (4, 2, 64, 32, 32)])
    def test_storm_replies_decode_to_bindings(self, batch, k, sub_nb,
                                              vlan_nb, cid_nb):
        """k full batches, one more, and a half one closed by the flush."""
        frames = storm_frames(batch * k + batch + batch // 2)
        sched = build_sched(build_fp(sub_nb, vlan_nb, cid_nb), batch,
                            express_aot=True)
        assert_codec_reads_bindings(run_express(sched, frames), frames)
        snap = sched.stats_snapshot()["express"]
        assert snap["aot_dispatches"] >= k + 2 and snap["aot_misses"] == 0

    def test_multi_round_identity_and_lease_state(self):
        """The chain threads batch to batch: later rounds see what the
        earlier ones wrote, identically on the AOT lane and on
        `_dhcp_jit`, and the codec reads the bindings every round."""
        frames = storm_frames(32)
        aot = build_sched(build_fp(), 8, express_aot=True)
        jit = build_sched(build_fp(), 8, express_aot=False)
        for _ in range(3):
            got = run_express(aot, frames)
            assert got == run_express(jit, frames)
            assert_codec_reads_bindings(got, frames)

    def test_two_fresh_stacks_are_byte_identical(self):
        frames = storm_frames(8 * 5 + 5)

        def sweep():
            sched = build_sched(build_fp(), 8, express_aot=True)
            out = [run_express(sched, frames) for _ in range(2)]
            sched.quiesce(now=float(NOW))
            return out, sched.stats_snapshot()["express"]

        out_a, snap_a = sweep()
        out_b, snap_b = sweep()
        assert out_a == out_b
        assert snap_a == snap_b
        assert_codec_reads_bindings(out_a[1], frames)

    def test_snapshot_surfaces_lane_stats(self):
        sched = build_sched(build_fp(), 8, express_aot=True)
        sched.process(storm_frames(32))
        snap = sched.stats_snapshot()["express"]
        assert snap["aot_enabled"] and snap["aot_dispatches"] >= 4
        assert snap["jit_dispatches"] == 0 and snap["aot_misses"] == 0
        assert snap["fallbacks"] == {}
        assert snap["queue_depth"] == 0 and snap["inflight"] == 0


# ---------------------------------------------------------------------------
# AOT cache: hit without retrace, miss falls back loudly
# ---------------------------------------------------------------------------

class TestAotCache:
    def test_geometry_hit_serves_without_retrace(self):
        sched = build_sched(build_fp(), 8, express_aot=True)
        frames = case_frames()
        run_express(sched, frames)  # warm (compile happened at init)
        traces = ex.TRACE_COUNT
        for k in range(3):
            out = run_express(sched, frames)
            assert len(out["tx"]) == 8
        assert ex.TRACE_COUNT == traces, "AOT geometry hit retraced"
        # compiled for THIS lane's device (its own when >1 attached)
        assert sched.engine.express_aot(8, sched._express_dev) is not None
        snap = sched.stats_snapshot()["express"]
        assert snap["aot_dispatches"] >= 4 and snap["jit_dispatches"] == 0

    def test_geometry_miss_falls_back_loudly(self, tmp_path):
        recorder = FlightRecorder(RecorderConfig(out_dir=str(tmp_path)))
        with tele.armed(recorder=recorder):
            sched = build_sched(build_fp(), 8, express_aot=True)
            run_express(sched, case_frames())  # healthy AOT round
            assert recorder.meta.get("express_program") == "aot-express"
            # a live lane re-tune changes the batch geometry; no AOT
            # program exists for it — the dispatch must fall back to
            # the jit-full path and say so everywhere
            sched.express.cfg.batch = 16
            out = run_express(sched, case_frames())
            assert len(out["tx"]) == 8  # correctness preserved
            assert sched.express_aot_misses == 1
            assert sched.express_jit_dispatches == 1
            assert recorder.triggers.get(TRIG_EXPRESS_AOT_MISS, 0) == 1
            assert recorder.dump_paths, "miss must leave a flight dump"
            assert recorder.meta.get("express_program") == "jit-full"
        # the miss counter reaches the metrics surface
        m = BNGMetrics()
        m.collect_scheduler(sched)
        text = m.registry.expose()
        assert "bng_express_aot_miss_total 1" in text
        assert ('bng_express_program_dispatches_total{program="jit-full"} 1'
                in text)

    def test_compile_failure_degrades_to_jit_loudly(self, monkeypatch):
        """A permanent AOT compile failure must not brick the lane OR
        keep paying the per-frame admission parse: descriptors stop
        being extracted, every dispatch counts as a miss, and the
        jit-full path serves correct replies."""
        from bng_tpu.runtime.engine import Engine

        def boom(self, batch, device=None):
            raise RuntimeError("mosaic said no")

        monkeypatch.setattr(Engine, "compile_express_aot", boom)
        sched = build_sched(build_fp(sub_nb=64, vlan_nb=32, cid_nb=32),
                            8, express_aot=True)
        assert not sched._aot_ready
        out = run_express(sched, case_frames())
        assert len(out["tx"]) == 8  # jit-full serves
        assert all(p is None or p.desc is None
                   for p in sched.express.q)  # no admission parse
        assert sched.express_aot_misses >= 1
        assert sched.express_jit_dispatches >= 1

    def test_env_kill_switch_disables_aot(self, monkeypatch):
        monkeypatch.setenv("BNG_EXPRESS_AOT", "0")
        sched = build_sched(build_fp(), 8, express_aot=True)
        out = run_express(sched, case_frames())
        assert len(out["tx"]) == 8
        snap = sched.stats_snapshot()["express"]
        assert not snap["aot_enabled"]
        assert snap["jit_dispatches"] >= 1 and snap["aot_misses"] == 0

    def test_retire_renders_from_dispatch_epoch_config(self):
        """A pool-config rewrite between dispatch and retire must not
        leak into the reply: the retire renders from the pool/server
        snapshot taken at dispatch (the epoch the device verdict was
        computed against), never the live mirrors."""
        fp = build_fp()
        sched = build_sched(fp, 8, express_aot=True)
        now = float(NOW)
        frame = dhcp_frame(mac_of(0), dhcp_codec.DISCOVER)
        assert sched.submit(frame, now=now, tag=0) == "express"
        pend, reason = sched.express.close_batch(now, "flush")
        sched._dispatch_express(pend, now, reason)  # in flight (depth 2)
        old_gw = ip_to_u32("10.0.0.1")
        fp.add_pool(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.254"),
                    ip_to_u32("9.9.9.9"), 0, 1800)  # config moves on
        sched._retire_express_all()
        (c,) = sched.drain_completions()
        p = dhcp_codec.decode(packets.decode(c.frame).payload)
        assert p.opt(dhcp_codec.OPT_ROUTER) == old_gw.to_bytes(4, "big")
        assert p.opt(dhcp_codec.OPT_LEASE_TIME) == (3600).to_bytes(4, "big")

    def test_aot_dispatch_folds_device_stats(self):
        from bng_tpu.ops.dhcp import ST_HIT

        sched = build_sched(build_fp(), 8, express_aot=True)
        run_express(sched, case_frames())
        assert int(sched.engine.stats.dhcp[ST_HIT]) == 8
        assert sched.engine.stats.tx == 8


# ---------------------------------------------------------------------------
# SLO wiring smoke: the device budget verdicts over express breakdowns
# ---------------------------------------------------------------------------

class TestSloSmoke:
    def test_device_budget_verdicts_express_breakdown(self):
        assert slo.HEADLINE_TARGETS["offer_device_only_p99_us"] == 50.0
        with tele.armed() as tracer:
            sched = build_sched(build_fp(), 8, express_aot=True)
            run_express(sched, case_frames())
            # profiler-fenced device samples under budget -> ok
            tracer.observe_many(tele.DEVICE, [12.0] * 64)
            assert slo.evaluate(tracer.breakdown())["ok"]
            # an excursion over the 50us paper target must breach
            tracer.observe_many(tele.DEVICE, [400.0] * 640)
            verdict = slo.evaluate(tracer.breakdown())
            assert not verdict["ok"] and "device" in verdict["breaches"]
