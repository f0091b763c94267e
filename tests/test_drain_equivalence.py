"""Only what changed is uploaded, and the device cannot tell (PR 35).

Two engines from one seed serve the same windows with the same host
writes between them. One drains as the tree does: a clean table answers
with the batch already on the chip, a dense array is placed again only
when its bytes changed. The other has every cache defeated before every
step: each generic table drains through the old body (six fresh arrays,
six uploads, dirty or not), every cached no-op batch and every placed
dense array is thrown away. After each step every verdict, every reply
and every leaf of the device's tables are equal. A write made between two
steps lands in the very next step on both.

Garden, PPPoE, edge and IPv6 tables are compiled in, so the drain walks
every table the engine can hold. Tiny sizes, CPU.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.control.nat import NATManager
from bng_tpu.control.pool import Pool, PoolManager
from bng_tpu.edge.tables import EdgeTables
from bng_tpu.ops.antispoof import MODE_STRICT
from bng_tpu.ops.table import HostTable
from bng_tpu.runtime.engine import (AntispoofTables, Engine, GardenTables,
                                    QoSTables)
from bng_tpu.runtime.tables import (FastPathTables, PPPoEFastPathTables,
                                    V6FastPathTables)
from bng_tpu.telemetry import spans
from bng_tpu.utils.net import ip_to_u32
from tests.test_table import _make_update_old_body

SERVER_MAC = bytes.fromhex("02aabbccdd01")
SERVER_IP = ip_to_u32("10.0.0.1")
T0 = 1_753_000_000
SUBS = 12
BATCH = 16
STEPS = 9
NH = bytes.fromhex("02eeee000001")


def _mac(i: int) -> bytes:
    return (0x02D0 << 32 | i).to_bytes(6, "big")


def _ip(i: int) -> int:
    return ip_to_u32("10.0.0.10") + i


def _stack():
    fastpath = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                              cid_nbuckets=64, max_pools=16)
    fastpath.set_server_config(SERVER_MAC, SERVER_IP)
    PoolManager(fastpath).add_pool(Pool(
        pool_id=1, network=ip_to_u32("10.0.0.0"), prefix_len=24,
        gateway=SERVER_IP, dns_primary=ip_to_u32("1.1.1.1"), lease_time=3600))
    nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                     sessions_nbuckets=256, sub_nat_nbuckets=64)
    qos = QoSTables(nbuckets=256)
    spoof = AntispoofTables(nbuckets=256)
    spoof.set_config(MODE_STRICT, log_violations=True)
    for i in range(SUBS):
        fastpath.add_subscriber(_mac(i), pool_id=1, ip=_ip(i),
                                lease_expiry=T0 + 86400)
        qos.set_subscriber(_ip(i), down_bps=8_000_000, up_bps=8_000_000)
        spoof.add_binding(_mac(i), _ip(i), MODE_STRICT)
        assert nat.allocate_nat(_ip(i), T0) is not None
        nat.handle_new_flow(_ip(i), ip_to_u32("93.184.216.34"), 40000 + i,
                            443, 17, 64, T0)
    engine = Engine(fastpath, nat, qos, spoof,
                    garden=GardenTables(nbuckets=64),
                    pppoe=PPPoEFastPathTables(nbuckets=64, stash=8),
                    edge=EdgeTables(tap_nbuckets=64, route_nbuckets=64),
                    v6=V6FastPathTables(spoof, nbuckets=64),
                    batch_size=BATCH, clock=lambda: float(T0))
    return engine


def _defeat_caches(engine) -> None:
    """Before a step of the reference engine: nothing is cached, so the
    batch is built and uploaded whole, as before this PR and worse."""
    for t in engine.host_mirror_tables().values():
        t.__dict__.pop("_empty_upd_cache", None)
        if isinstance(t, HostTable):  # the QoS table's clean answer is PR 33's
            t.make_update = lambda n, t=t: _make_update_old_body(t, n)
    for owner in (engine.fastpath, engine.nat, engine.antispoof,
                  engine.garden, engine.edge):
        owner.__dict__.pop("_placed", None)


def _discover(i: int, xid: int) -> bytes:
    p = dhcp_codec.build_request(_mac(i), dhcp_codec.DISCOVER, xid=xid)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(_mac(i), b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _up(i: int, sport: int | None = None, dst: str = "93.184.216.34") -> bytes:
    return packets.udp_packet(_mac(i), SERVER_MAC, _ip(i), ip_to_u32(dst),
                              40000 + i if sport is None else sport, 443,
                              b"drain-equivalence")


def _window(k: int):
    """The frames of step k: renewing DISCOVERs (device replies), NAT'd
    data of provisioned flows, and the subscribers the writes touch: 20
    (leased at step 1, deleted at step 6), 21 (a NAT block and a flow at
    step 2), 3 (gardened at step 5), 4 (routed and tapped at step 7)."""
    frames = [_discover((k + j) % SUBS, 0x3500 + 16 * k + j) for j in range(3)]
    frames += [_up((2 * k + j) % SUBS) for j in range(4)]
    frames += [_discover(20, 0x3600 + k), _up(21, sport=45000),
               _up(3, dst="198.51.100.7"), _up(4)]
    return frames


def _writes(k: int, e) -> None:
    """The host writes made before step k, one kind a step and all of them
    at step 8: a lease added and deleted, a NAT allocation with its flow,
    an antispoof range and a config flip, a pool, the server address, a
    garden member and its allowed destination, a NAT hairpin, a route and
    a tap with its filter, a PPPoE session, an IPv6 binding, a QoS plan."""
    every = k == 8
    if k == 1 or every:
        e.fastpath.add_subscriber(_mac(20 + 10 * every), pool_id=1,
                                  ip=_ip(20 + 10 * every),
                                  lease_expiry=T0 + 600)
        e.antispoof.add_binding(_mac(20 + 10 * every), _ip(20 + 10 * every),
                                MODE_STRICT)
    if k == 2 or every:
        i = 21 + 10 * every
        e.antispoof.add_binding(_mac(i), _ip(i), MODE_STRICT)
        assert e.nat.allocate_nat(_ip(i), T0 + k) is not None
        e.nat.handle_new_flow(_ip(i), ip_to_u32("93.184.216.34"), 45000, 443,
                              17, 64, T0 + k)
    if k == 3 or every:
        e.antispoof.add_allowed_range(ip_to_u32("172.16.0.0") + (every << 16),
                                      16)
        e.antispoof.set_config(MODE_STRICT, log_violations=not every)
    if k == 4 or every:
        e.fastpath.add_pool(2 + every, ip_to_u32("10.2.0.0") + (every << 16),
                            24, ip_to_u32("10.2.0.1"))
        e.fastpath.set_server_config(SERVER_MAC, SERVER_IP + 1 + every)
    if k == 5 or every:
        e.garden.set_gardened(_ip(3 + every), True)
        e.garden.allow_destination(ip_to_u32("198.51.100.7") + every, 443, 17)
        e.nat.add_hairpin_ip(ip_to_u32("203.0.113.1") + every)
        e.nat.add_alg_port(21 + every, 6)
    if k == 6 or every:
        gone = 0 if every else 20
        assert e.fastpath.remove_subscriber(_mac(gone))
        assert e.antispoof.remove_binding(_mac(gone))
        e.qos.set_subscriber(_ip(5 + every), down_bps=64_000, up_bps=64_000)
    if k == 7 or every:
        e.edge.set_route(_ip(4 + every), NH, 100 + every, 1)
        e.edge.arm_tap(_ip(4 + every), 7 + every, [(443, 17, 0)])
        e.pppoe.session_up(SimpleNamespace(
            session_id=0x40 + every, client_mac=_mac(50 + every),
            assigned_ip=_ip(50 + every)))
        e.v6.bind(_mac(6 + every), bytes.fromhex("20010db8000100000000000000000007")
                  [:15] + bytes([7 + every]), ipv4=_ip(6 + every))


def _leaves(engine):
    return [(jax.tree_util.keystr(kp), np.asarray(x)) for kp, x in
            jax.tree_util.tree_flatten_with_path(engine.tables)[0]]


@pytest.mark.hotpath
def test_drain_with_and_without_caches_leaves_the_same_device():
    new, ref = _stack(), _stack()
    mirrored = {"new": [], "ref": []}
    new.mirror_sink = lambda lane, frame, wid: mirrored["new"].append((lane, wid))
    ref.mirror_sink = lambda lane, frame, wid: mirrored["ref"].append((lane, wid))
    n_tables = len(new.host_mirror_tables())
    assert n_tables == 15  # every table an engine can hold
    lease_lane = 7  # subscriber 20's DISCOVER in every window
    for k in range(STEPS):
        _writes(k, new)
        _writes(k, ref)
        dirty = sum(1 for t in new.host_mirror_tables().values()
                    if t.dirty_count())
        # step 0 follows no write; steps 3 and 4 write dense arrays alone
        assert (dirty > 0) == (k not in (0, 3, 4)), k
        _defeat_caches(ref)
        frames = _window(k)
        with spans.armed() as tr:
            got = new.process(frames, now=T0 + 0.02 * k)
        # a batch was built for each dirty table and for no other
        assert tr.sums()["drain_built"] == dirty, k
        assert tr.sums()["drain_cached"] == n_tables - dirty, k
        want = ref.process(frames, now=T0 + 0.02 * k)
        assert got == want, k
        for (name, a), (_n, b) in zip(_leaves(new), _leaves(ref)):
            assert (a == b).all(), (k, name)
        assert new.pending_dirty() == ref.pending_dirty(), k
        for name in ("dhcp", "nat", "qos", "spoof", "garden", "pppoe",
                     "edge", "v6"):
            assert (np.asarray(getattr(new.stats, name))
                    == np.asarray(getattr(ref.stats, name))).all(), (k, name)
        # a write made between two steps is in the very next step: the
        # lease written before step 1 answers on the device in step 1, and
        # the delete before step 6 takes it away in step 6
        replied = lease_lane in [lane for lane, _f in got["tx"]]
        assert replied == (1 <= k < 6), k
    assert mirrored["new"] == mirrored["ref"] and mirrored["new"]
    assert new.stats.tx > 0 and new.stats.fwd > 0
