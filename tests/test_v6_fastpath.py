"""The device IPv6 stage (ops/v6.py, `bng run --ipv6-fastpath`) against the
plain reference, and its control plane through the engine's ring.

(a) `pipeline_step`, through `Engine.process`, agrees with
`benchmark/kits/dualstack.py Plain.verdict_of` on every IPv6 lane of seeded
random batches: verdict and bytes exact (there is no floating point here).
The reference is written from the deployment's rules with `struct` and
`ipaddress` over two dicts; nothing of `bng_tpu/ops` is in it. IPv4 NAT and
DHCP lanes of the same batches are what the program without the stage gives.

(b) the control plane under strict mode: a SOLICIT from fe80:: reaches the
`SlowPathDemux`, the REPLY's lease publishes binding and by-address row,
the next data frame forwards both ways, RELEASE and expiry take both out,
and a checkpoint written before restores after.

Seeded random tables and frames, tiny sizes, CPU.
"""

import ipaddress
import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.kits.dualstack import DROP, FORWARD, PASS, Plain  # noqa: E402
from bng_tpu.control import dhcp_codec, packets  # noqa: E402
from bng_tpu.control.nat import NATManager  # noqa: E402
from bng_tpu.control.pool import Pool, PoolManager  # noqa: E402
from bng_tpu.ops import antispoof as A  # noqa: E402
from bng_tpu.runtime import hostpath  # noqa: E402
from bng_tpu.runtime.engine import AntispoofTables, Engine, QoSTables  # noqa: E402
from bng_tpu.runtime.tables import (FastPathTables, V6FastPathTables,  # noqa: E402
                                    v6_words)
from bng_tpu.utils.net import ip_to_u32  # noqa: E402

SERVER_MAC = bytes.fromhex("02aabbccdd01")
ROUTER_MAC = bytes.fromhex("02ee00000001")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000
SUBS = 24
BATCH = 64
MODE_OF = {"disabled": A.MODE_DISABLED, "strict": A.MODE_STRICT,
           "loose": A.MODE_LOOSE, "log-only": A.MODE_LOG_ONLY}
TAGS = {0: b"", 1: bytes([0x81, 0x00, 0x00, 100]),
        2: bytes([0x88, 0xA8, 0x00, 7, 0x81, 0x00, 0x00, 100])}


def v6(text: str) -> bytes:
    return ipaddress.IPv6Address(text).packed


def frame6(src_mac, dst_mac, src, dst, payload, tags=0, next_header=17):
    """Eth [+ tags] + IPv6 + `payload` as the upper layer."""
    return (dst_mac + src_mac + TAGS[tags] + b"\x86\xdd"
            + bytes([0x60, 0, 0, 0]) + len(payload).to_bytes(2, "big")
            + bytes([next_header, 64]) + src + dst + payload)


class Stack:
    """One engine over seeded random tables. Subscriber i has a DHCP row, a
    QoS row, a NAT block and two flows; two in three hold a /128 beside the
    v4 binding, the rest a v4 binding alone. The reference's two dicts are
    filled beside the tables, by nothing the tables compute."""

    def __init__(self, seed, stage=True, mode="strict", burst=1 << 20):
        rng = np.random.default_rng(seed)
        fastpath = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                                  cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(SERVER_MAC, SERVER_IP)
        PoolManager(fastpath).add_pool(Pool(
            pool_id=1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
            gateway=SERVER_IP, dns_primary=ip_to_u32("1.1.1.1"),
            lease_time=3600))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        qos = QoSTables(nbuckets=256)
        spoof = AntispoofTables(nbuckets=256)
        spoof.set_config(MODE_OF[mode], log_violations=True)
        self.v6t = V6FastPathTables(spoof, nbuckets=256) if stage else None
        self.macs = [bytes([0x02, *rng.integers(0, 256, 5).tolist()])
                     for _ in range(SUBS)]
        self.ips = [int(x) for x in
                    ip_to_u32("10.0.0.10") + rng.permutation(200)[:SUBS]]
        self.addrs = [v6(f"2001:db8:1::{int(x):x}") if i % 3 else None
                      for i, x in enumerate(rng.permutation(60000)[:SUBS] + 1)]
        self.bindings, self.by_addr, self.flows = {}, {}, []
        for mac, ip, addr in zip(self.macs, self.ips, self.addrs):
            fastpath.add_subscriber(mac, pool_id=1, ip=ip,
                                    lease_expiry=T0 + 86400)
            qos.set_subscriber(ip, down_bps=80_000, up_bps=80_000,
                               down_burst=burst, up_burst=burst)
            spoof.add_binding(mac, ip, MODE_OF[mode])
            self.bindings[mac] = (ip, addr, mode)
            if addr is not None:
                self.by_addr[addr] = ip
                if stage:
                    self.v6t.bind(mac, addr)
                else:  # the binding row alone, as the parent could hold it
                    spoof.add_binding_v6(mac, v6_words(addr), MODE_OF[mode])
            assert nat.allocate_nat(ip, T0) is not None
            for _ in range(2):
                dst = int(ip_to_u32("93.184.0.0") + rng.integers(1, 60000))
                sport = int(rng.integers(20000, 60000))
                proto = int(rng.choice([6, 17]))
                nat_ip, nat_port = nat.handle_new_flow(ip, dst, sport, 443,
                                                       proto, 64, T0)
                self.flows.append((mac, ip, dst, sport, proto, nat_ip,
                                   nat_port))
        self.engine = Engine(fastpath, nat, qos, spoof, batch_size=BATCH,
                             clock=lambda: float(T0), v6=self.v6t)
        self.plain = Plain(self.bindings, self.by_addr, default_mode=mode)
        self.bound = [i for i, a in enumerate(self.addrs) if a is not None]
        self.v4_only = [i for i, a in enumerate(self.addrs) if a is None]


def peer(rng) -> bytes:
    return v6(f"2001:db8:ffff::{int(rng.integers(1, 60000)):x}")


def l4(rng) -> bytes:
    return bytes(rng.integers(0, 256, int(rng.integers(8, 120)),
                              dtype=np.uint8))


# every kind of IPv6 lane the rules name: (name, from_access, builder)
def v6_lane(kind, st, rng, tags):
    i = int(rng.choice(st.bound))
    mac, addr = st.macs[i], st.addrs[i]
    up = lambda src_mac, src, dst, pre=b"", **kw: (  # noqa: E731
        frame6(src_mac, SERVER_MAC, src, dst, pre + l4(rng), tags, **kw), True)
    down = lambda src, dst: (  # noqa: E731
        frame6(ROUTER_MAC, SERVER_MAC, src, dst, l4(rng), tags), False)
    if kind == "bound":
        return up(mac, addr, peer(rng))
    if kind == "unbound":
        return up(bytes([0x06, *rng.integers(0, 256, 5).tolist()]), addr,
                  peer(rng))
    if kind == "wrong-source":
        other = st.addrs[int(rng.choice([j for j in st.bound if j != i]))]
        return up(mac, other, peer(rng))
    if kind == "v4-only":
        return up(st.macs[int(rng.choice(st.v4_only))], addr, peer(rng))
    if kind == "link-local":
        return up(mac, v6("fe80::1"), v6("ff02::1:2"))  # a SOLICIT's addresses
    if kind == "unspecified":
        return up(mac, v6("::"), v6("ff02::1:ff00:42"))  # DAD's NS
    if kind == "multicast":
        return up(mac, addr, v6("ff02::16"), next_header=58)  # MLD report
    if kind == "to-link-local":
        return up(mac, addr, v6("fe80::2"), next_header=58)  # NA to the router
    if kind == "hop-by-hop":
        ext = bytes([17, 0, 1, 4, 0, 0, 0, 0])  # next UDP, PadN
        return up(mac, addr, peer(rng), pre=ext, next_header=0)
    if kind == "cut":  # the fixed header is not whole
        f, fa = up(mac, addr, peer(rng))
        return f[:14 + len(TAGS[tags]) + int(rng.integers(1, 40))], fa
    if kind == "down-bound":
        return down(peer(rng), addr)
    if kind == "down-unknown":
        return down(peer(rng), v6(f"2001:db8:1::f:{int(rng.integers(1, 9999)):x}"))
    if kind == "down-multicast":
        return down(peer(rng), v6("ff02::1"))
    if kind == "down-from-link-local":  # rule 3 asks the destination alone
        return down(v6("fe80::9"), addr)
    raise AssertionError(kind)


KINDS = ("bound", "unbound", "wrong-source", "v4-only", "link-local",
         "unspecified", "multicast", "to-link-local", "hop-by-hop", "cut",
         "down-bound", "down-unknown", "down-multicast",
         "down-from-link-local")


def v4_lane(st, rng):
    """A NAT data frame (either direction) or a DHCP request."""
    if rng.random() < 0.25:
        i = int(rng.integers(0, SUBS))
        p = dhcp_codec.build_request(st.macs[i], dhcp_codec.REQUEST,
                                     xid=int(rng.integers(1, 2**31)),
                                     requested_ip=st.ips[i],
                                     server_id=SERVER_IP)
        p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                          bytes([1, 3, 6, 51, 54])))
        return packets.udp_packet(st.macs[i], b"\xff" * 6, 0, 0xFFFFFFFF, 68,
                                  67, p.encode().ljust(320, b"\x00")), True
    mac, ip, dst, sport, proto, nat_ip, nat_port = st.flows[
        int(rng.integers(0, len(st.flows)))]
    make = packets.udp_packet if proto == 17 else packets.tcp_packet
    if rng.random() < 0.5:
        return make(mac, SERVER_MAC, ip, dst, sport, 443, l4(rng)), True
    return make(ROUTER_MAC, SERVER_MAC, dst, nat_ip, 443, nat_port,
                l4(rng)), False


def run(engine, lanes):
    """{lane: (verdict, bytes that left or None)} of one batch."""
    frames, fa = [f for f, _ in lanes], [a for _, a in lanes]
    out = engine.process(frames, from_access=fa, now=float(T0))
    got = {i: (DROP, None) for i in out["dropped"]}
    got.update({i: (PASS, None) for i, _reply in out["slow"]})
    got.update({i: (FORWARD, raw) for i, raw in out["fwd"]})
    got.update({i: ("tx", raw) for i, raw in out["tx"]})
    assert sorted(got) == list(range(len(frames)))
    return got


def batch(st, rng, tags, kinds=KINDS):
    order = rng.permutation(np.repeat(np.arange(len(kinds)), 3))
    lanes, names = [], []
    for k in order:
        lanes.append(v6_lane(kinds[k], st, rng, tags))
        names.append(kinds[k])
        if rng.random() < 0.3 and len(lanes) < BATCH - 1:
            lanes.append(v4_lane(st, rng))
            names.append("v4")
    return lanes[:BATCH], names[:BATCH]


@pytest.mark.parametrize("tags", [0, 1, 2])
@pytest.mark.parametrize("mode", list(MODE_OF))
def test_every_v6_lane_gets_the_plain_references_verdict_and_bytes(mode, tags):
    seed = 7000 + 10 * tags + list(MODE_OF).index(mode)
    st, bare = Stack(seed, mode=mode), Stack(seed, stage=False, mode=mode)
    rng = np.random.default_rng([seed, 1])
    seen = set()
    for _ in range(2):
        lanes, names = batch(st, rng, tags)
        got, without = run(st.engine, lanes), run(bare.engine, lanes)
        for i, ((frame, fa), name) in enumerate(zip(lanes, names)):
            if name == "v4":
                # what the program without the stage gives, bytes and all
                assert got[i] == without[i], (name, i)
                continue
            want = st.plain.verdict_of(frame, fa)
            verdict, raw = got[i]
            if want is None:  # not IPv6 with its header whole: the host's
                assert name == "cut" and verdict == PASS
                continue
            assert verdict == want[0], (name, mode, tags, i)
            assert raw == (frame if verdict == FORWARD else None), (name, i)
            seen.add((name, verdict))
    # the rules' cases all came up, with the verdicts this mode gives them
    strict_like = {"strict": DROP, "loose": DROP, "log-only": PASS,
                   "disabled": PASS}[mode]
    assert {("bound", FORWARD), ("hop-by-hop", FORWARD),
            ("down-bound", FORWARD), ("down-from-link-local", FORWARD),
            ("down-unknown", PASS), ("down-multicast", PASS),
            ("link-local", PASS), ("unspecified", PASS), ("multicast", PASS),
            ("to-link-local", PASS), ("wrong-source", strict_like),
            ("unbound", PASS if mode in ("loose", "disabled", "log-only")
             else DROP),
            ("v4-only", PASS if mode in ("loose", "disabled", "log-only")
             else DROP)} <= seen, sorted(seen)
    fwd_up, fwd_down, miss, ctrl = (int(x) for x in st.engine.stats.v6)
    assert fwd_up > 0 and fwd_down > 0 and miss > 0 and ctrl > 0
    assert bare.engine.stats.v6.sum() == 0


@pytest.mark.parametrize("direction", ["up", "down"])
def test_one_subscribers_v4_and_v6_bytes_empty_one_bucket(direction):
    """Alternating IPv4 (NAT) and IPv6 frames of one subscriber, 100 bytes
    each on the wire, against a bucket of 450 bytes: the first four leave,
    whichever family they are, and the drops fall on both families."""
    st = Stack(8100, burst=450)
    i = st.bound[0]
    mac, ip, addr = st.macs[i], st.ips[i], st.addrs[i]
    _mac, _ip, dst, sport, proto, nat_ip, nat_port = next(
        f for f in st.flows if f[0] == mac)
    make = packets.udp_packet if proto == 17 else packets.tcp_packet
    rng = np.random.default_rng(5)
    lanes = []
    for k in range(10):
        if k % 2 == 0 and direction == "up":
            f = make(mac, SERVER_MAC, ip, dst, sport, 443, b"")
        elif k % 2 == 0:
            f = make(ROUTER_MAC, SERVER_MAC, dst, nat_ip, 443, nat_port, b"")
        elif direction == "up":
            f = frame6(mac, SERVER_MAC, addr, peer(rng), b"")
        else:
            f = frame6(ROUTER_MAC, SERVER_MAC, peer(rng), addr, b"")
        lanes.append((f + bytes(100 - len(f)), direction == "up"))
    # another subscriber's frames in between draw on another bucket
    j = st.bound[1]
    other = (frame6(st.macs[j], SERVER_MAC, st.addrs[j], peer(rng), bytes(60)),
             True)
    lanes[5:5] = [other]
    got = run(st.engine, lanes)
    verdicts = [got[k][0] for k in range(len(lanes)) if k != 5]
    assert verdicts == [FORWARD] * 4 + [DROP] * 6
    assert got[5][0] == FORWARD
    # both families among the drops, and all of them counted as QoS drops
    from bng_tpu.ops.qos import QST_PKTS_DROPPED

    assert int(st.engine.stats.qos[QST_PKTS_DROPPED]) == 6
    assert st.engine.stats.dropped == 6
    # an admitted lane out of tokens is not counted as forwarded
    assert int(st.engine.stats.v6[:2].sum()) == 2 + 1


def test_a_v6_lane_leaves_nat_dhcp_and_garden_alone():
    """Rule 4: a batch of IPv6 lanes moves no NAT session counter and no
    DHCP stat, and the QoS buckets of the family's other direction stay."""
    st = Stack(8200)
    rng = np.random.default_rng(6)
    lanes = [v6_lane(k, st, rng, 0) for k in ("bound", "down-bound") * 8]
    sessions_before = np.asarray(st.engine.tables.nat.sessions.vals).copy()
    run(st.engine, lanes)
    assert (np.asarray(st.engine.tables.nat.sessions.vals)
            == sessions_before).all()
    assert st.engine.stats.dhcp.sum() == 0
    assert int(st.engine.stats.v6[0]) == 8 and int(st.engine.stats.v6[1]) == 8


def _step_hlo(st) -> str:
    eng = st.engine
    return str(eng._step.lower(
        eng.tables,
        jnp.zeros((hostpath.window_rows(BATCH, eng.L), eng.L), jnp.uint8),
        np.uint32(1), np.uint32(1)
    ).compiler_ir(dialect="stablehlo"))


# (index operand's shape, result's shape, result's type) of every gather; an
# index of shape `1` is a static column pick (`x[:, 3]`), no per-lane gather
GATHER = re.compile(r'"stablehlo\.gather"[^\n]*tensor<([0-9x]+)xi32>\) '
                    r'-> tensor<([0-9x]+)x(\w+)>')


def test_the_stage_adds_no_while_and_no_wide_byte_gather():
    """What the stage costs on the chip is a select among three static
    slices and one table probe. A per-lane index over packet bytes is a
    gather, which moves one byte an index (PERF.md section 6, PR 26, 31):
    the step with the stage holds the `ui8` gathers the step without it
    holds, antispoof's 16-byte source read the only one wider than a byte,
    and three more per-lane `ui32` row gathers (two bucket rows, one value
    row)."""
    with_stage, without = (GATHER.findall(_step_hlo(Stack(8300, stage=s)))
                           for s in (True, False))
    assert without, "the pattern no longer finds the step's gathers"
    u8 = lambda found: sorted(d for _i, d, ty in found if ty == "ui8")  # noqa: E731
    assert u8(with_stage) == u8(without)
    assert [d for d in u8(with_stage) if not d.endswith("x1")] == [
        f"{BATCH}x16"]
    rows = lambda found: sorted(d for i, d, ty in found  # noqa: E731
                                if ty == "ui32" and i != "1")
    added = rows(with_stage)
    for d in rows(without):
        added.remove(d)
    assert added == [f"{BATCH}x32", f"{BATCH}x32", f"{BATCH}x8"]
    hlo = _step_hlo(Stack(8300))
    assert "stablehlo.while" not in hlo
    assert "dynamic_slice" not in hlo


def test_without_the_stage_no_v6_op_is_compiled():
    st = Stack(8400, stage=False)
    assert st.engine.tables.v6_by_addr is None and st.engine.geom.v6 is None
    lanes = [v6_lane("bound", st, np.random.default_rng(1), 0)]
    got = run(st.engine, lanes)
    # the binding row matches, nothing forwards: the host's, as before
    assert got[0] == (PASS, None)
    assert st.engine.stats.v6.sum() == 0


# --------------------------------------------------------------------------
# (b) the control plane, through `bng run`'s app and the engine's ring loop
# --------------------------------------------------------------------------

from bng_tpu.cli import BNGApp, BNGConfig  # noqa: E402
from bng_tpu.control.dhcpv6 import protocol as p6  # noqa: E402
from bng_tpu.control.dhcpv6.protocol import (DHCPv6Message, IAAddress,  # noqa: E402
                                             IANA, generate_duid_ll)
from bng_tpu.runtime import checkpoint as ck  # noqa: E402
from bng_tpu.runtime.ring import PyRing  # noqa: E402

CLIENT_MAC = bytes.fromhex("02cc00000042")
CLIENT_V4 = ip_to_u32("10.0.1.7")
CLIENT_LL = v6("fe80::cc:ff:fe00:42")
ALL_AGENTS = v6("ff02::1:2")
LEASE_TIME = 600


class App:
    """`bng run --ipv6-fastpath` at a tiny size, strict antispoof, one
    provisioned IPv4 subscriber; frames in and out by the ring, a clock
    the test moves."""

    def __init__(self):
        self.now = float(T0)
        cfg = BNGConfig(ipv6_fastpath=True, slaac_enabled=False,
                        walled_garden_enabled=False, metrics_enabled=False,
                        batch_size=8, lease_time=LEASE_TIME)
        self.app = BNGApp(cfg, clock=lambda: self.now)
        self.ring = self.app.components["ring"] = PyRing(
            nframes=128, frame_size=2048, depth=32)
        c = self.c = self.app.components
        c["antispoof"].set_config(A.MODE_STRICT, log_violations=True)
        c["antispoof"].add_binding(CLIENT_MAC, CLIENT_V4, A.MODE_STRICT)
        c["fastpath"].add_subscriber(CLIENT_MAC, pool_id=1, ip=CLIENT_V4,
                                     lease_expiry=T0 + 86400)
        self.xid = 0x100

    def offer(self, frame, from_access=True):
        """One frame through the loop: (replies on TX, frames forwarded)."""
        assert self.ring.rx_push(frame, from_access=from_access)
        for _ in range(3):  # the pipelined loop retires a beat later
            self.app.drive_once()
        tx, fwd = [], []
        while (got := self.ring.tx_pop()) is not None:
            tx.append(got[0])
        while (got := self.ring.fwd_pop()) is not None:
            fwd.append(got[0])
        return tx, fwd

    def dhcpv6(self, msg_type, server_duid=None, addr=None):
        """One client message from the link-local address to ff02::1:2;
        returns the decoded reply."""
        self.xid += 1
        m = DHCPv6Message(msg_type, self.xid)
        m.add(p6.OPT_CLIENTID, generate_duid_ll(CLIENT_MAC).encode())
        if server_duid is not None:
            m.add(p6.OPT_SERVERID, server_duid)
        ia = IANA(1)
        if addr is not None:
            ia.addresses.append(IAAddress(addr, 0, 0))
        m.add_ia_na(ia)
        if msg_type == p6.SOLICIT:
            m.add(p6.OPT_RAPID_COMMIT, b"")
        frame = packets.udp6_packet(CLIENT_MAC, bytes.fromhex("333300010002"),
                                    CLIENT_LL, ALL_AGENTS, 546, 547,
                                    m.encode())
        tx, fwd = self.offer(frame)
        assert len(tx) == 1 and not fwd, (tx, fwd)
        assert tx[0][:6] == CLIENT_MAC and tx[0][12:14] == b"\x86\xdd"
        return DHCPv6Message.decode(tx[0][14 + 40 + 8:])

    def lease(self) -> tuple[bytes, bytes]:
        reply = self.dhcpv6(p6.SOLICIT)
        assert reply.msg_type == p6.REPLY
        return reply.ia_nas()[0].addresses[0].address, reply.server_duid

    def data(self, addr):
        """(upstream forwarded?, downstream forwarded?) byte for byte."""
        remote = v6("2001:db8:ffff::9")
        up = frame6(CLIENT_MAC, SERVER_MAC, addr, remote, bytes(24))
        down = frame6(ROUTER_MAC, SERVER_MAC, remote, addr, bytes(24))
        return (self.offer(up)[1] == [up],
                self.offer(down, from_access=False)[1] == [down])

    def bound(self, addr) -> bool:
        """Binding row and by-address row, both or neither."""
        row = self.c["antispoof"].bindings.lookup(
            [int.from_bytes(CLIENT_MAC[:2], "big"),
             int.from_bytes(CLIENT_MAC[2:], "big")])
        has_v6 = bool(row[A.AB_VALIDS] & A.VALID_V6)
        val = self.c["v6_tables"].by_addr.lookup(v6_words(addr))
        assert has_v6 == (val is not None)
        if has_v6:
            assert (row[A.AB_V6_0:A.AB_V6_0 + 4] == v6_words(addr)).all()
            assert int(val[0]) == CLIENT_V4 == int(row[A.AB_IPV4])
        return has_v6


@pytest.fixture(scope="module")
def app():
    a = App()
    yield a
    a.app.close()


def test_a_dhcpv6_lease_reaches_the_device_tables_and_leaves_them(app):
    assert app.c["engine"].tables.v6_by_addr is not None
    passed = app.c["engine"].stats.passed
    # under strict mode the SOLICIT from fe80:: is no violation: it reaches
    # the demux, and the REPLY's lease publishes binding and row
    addr, server_duid = app.lease()
    assert app.c["slowpath"].stats["dhcp6"] == 1
    assert app.c["engine"].stats.passed == passed + 1
    assert int(app.c["engine"].stats.v6[3]) == 1  # counted as control
    assert ipaddress.IPv6Address(addr) in ipaddress.IPv6Network(
        app.app.config.dhcpv6_prefix)
    assert app.bound(addr)
    # the next data frame forwards both ways, byte for byte
    assert app.data(addr) == (True, True)
    # RELEASE takes both out: upstream is a strict violation, downstream a
    # miss the host has no answer for
    reply = app.dhcpv6(p6.RELEASE, server_duid, addr)
    assert reply.msg_type == p6.REPLY
    assert not app.bound(addr)
    dropped = app.c["engine"].stats.dropped
    assert app.data(addr) == (False, False)
    assert app.c["engine"].stats.dropped == dropped + 1
    assert int(app.c["engine"].stats.v6[2]) == 1  # the downstream miss
    # a new lease, and a spoofed source beside it
    addr, server_duid = app.lease()
    assert app.bound(addr) and app.data(addr) == (True, True)
    other = (int.from_bytes(addr, "big") + 5).to_bytes(16, "big")
    assert app.data(other) == (False, False)
    # expiry takes both out, through tick()'s sweep
    app.now += 2 * LEASE_TIME + 1
    app.app._last_expire = -1e18
    app.app.tick(app.now)
    assert not app.c["dhcpv6"].leases and not app.bound(addr)
    assert app.data(addr) == (False, False)
    # DECLINE: the binding goes as on a release
    addr, server_duid = app.lease()
    assert app.bound(addr)
    app.dhcpv6(p6.DECLINE, server_duid, addr)
    assert not app.bound(addr)
    stats = app.app.stats()["ipv6_fastpath"]
    assert stats["bound"] == 0 and stats["device"]["fwd_up"] == 2


def test_a_renumbered_subscribers_old_address_stops_matching(app):
    addr, server_duid = app.lease()
    assert app.data(addr) == (True, True)
    # the same MAC under another client id is leased another address
    app.c["slowpath"].dhcpv6_requester = CLIENT_MAC
    try:
        new = app.c["dhcpv6"]._grant_na(b"\x00\x03\x00\x01another", IANA(9),
                                        commit=True).addresses[0].address
    finally:
        app.c["slowpath"].dhcpv6_requester = None
    assert new != addr and app.bound(new)
    assert app.c["v6_tables"].by_addr.lookup(v6_words(addr)) is None
    assert app.data(addr) == (False, False) and app.data(new) == (True, True)
    # both leases end: the one the device no longer held changes nothing
    app.dhcpv6(p6.RELEASE, server_duid, addr)
    assert app.bound(new)
    app.c["dhcpv6"]._drop_binding(b"\x00\x03\x00\x01another", 9, is_pd=False)
    assert not app.bound(new) and not app.c["dhcpv6"].leases


def test_a_checkpoint_written_before_restores_after(app):
    addr, _ = app.lease()
    assert app.data(addr) == (True, True)
    snap = ck.roundtrip_checkpoint(ck.build_checkpoint(
        1, app.now, engine=app.c["engine"]))
    assert "v6" in snap.meta["components"]
    after = App()
    try:
        assert not after.bound(addr)
        rows = ck.restore_checkpoint(snap, engine=after.c["engine"])
        assert rows["v6.by_addr"] == 1
        assert after.bound(addr) and after.data(addr) == (True, True)
        # a program without the stage refuses the component, whole
        bare = BNGApp(BNGConfig(slaac_enabled=False, metrics_enabled=False,
                                walled_garden_enabled=False, batch_size=8))
        try:
            with pytest.raises(ck.CheckpointError, match="v6"):
                ck.restore_checkpoint(snap, engine=bare.components["engine"])
        finally:
            bare.close()
    finally:
        after.app.close()


@pytest.mark.parametrize("flags,where", [
    ({"shards": 2}, "sharded_blockers"),
    ({"slowpath_workers": 2, "slowpath_worker_mode": "inline"},
     "fleet_blockers")])
def test_the_flag_is_a_named_blocker_where_the_stage_is_not_wired(flags, where):
    cfg = BNGConfig(ipv6_fastpath=True, slaac_enabled=False,
                    walled_garden_enabled=False, metrics_enabled=False,
                    batch_size=8, shard_nbuckets=64, **flags)
    a = BNGApp(cfg)
    try:
        assert "ipv6-fastpath" in getattr(a, where)
        if where == "fleet_blockers":
            assert "fleet" not in a.components  # collapsed, and said so
    finally:
        a.close()


def test_the_flag_is_off_by_default_and_sizes_its_table_like_the_others():
    from bng_tpu.ops.table import nbuckets_for

    off = BNGApp(BNGConfig(slaac_enabled=False, metrics_enabled=False,
                           walled_garden_enabled=False, batch_size=8))
    try:
        assert BNGConfig().ipv6_fastpath is False and BNGConfig().dhcpv6_enabled
        assert "v6_tables" not in off.components
        assert off.components["engine"].tables.v6_by_addr is None
        assert off.components["dhcpv6"].on_lease is None
    finally:
        off.close()
    from bng_tpu.runtime.tables import V6FastPathTables as T

    t = T(AntispoofTables(nbuckets=64), nbuckets=nbuckets_for(1_000_000))
    assert t.by_addr.nbuckets == 524_288 and t.by_addr.KW == 8
