"""The flows a retired window punted are opened in one batch (PR 54), and the
state it leaves is the one-by-one path's.

Three twins of one provisioned CGNAT manager serve the same seeded frames in
lane order:

- `batch`: `NewFlows.punt_many` over the whole list (one decode pass, one
  `NATManager.handle_new_flows`, one placement a table);
- `single`: `packets.decode` and `NATManager.handle_new_flow`, the batch of
  one, a frame at a time;
- `parent`: the loop this tree ran until PR 54, kept here as the reference
  (`parent_handle_new_flow`: `HostTable.lookup` / `insert` a flow).

Equal afterwards: the answers, `sessions` and `reverse` (whole, key for key;
by `lookup` of every key, by `lookup_many`, and `count`), `eim`, `_ext_ports`,
every block's `next_port`, `exhausted`, the compliance log entry for entry in
order, and what `make_updates()` ships for the batch's keys, which applied to
the device tables makes the chip answer for every flow opened.
"""

import struct

import jax.numpy as jnp
import numpy as np
import pytest

from bng_tpu.control import packets as P
from bng_tpu.control.nat import (LOG_PORT_EXHAUSTION, LOG_SESSION_CREATE,
                                 NATExhaustedError, NATManager,
                                 apply_nat_updates)
from bng_tpu.ops.nat44 import (FLAG_EIM, FLAG_PORT_PARITY, NAT_STATE_NEW,
                               REVERSE_WORDS, SESSION_WORDS, SV_BYTES_OUT,
                               SV_CREATED, SV_DEST_IP, SV_DEST_PORT,
                               SV_LAST_SEEN, SV_NAT_IP, SV_NAT_PORT,
                               SV_ORIG_IP, SV_ORIG_PORT, SV_PKTS_OUT, SV_PROTO,
                               SV_STATE)
from bng_tpu.ops.table import device_lookup
from bng_tpu.runtime.newflow import NewFlows, flow_of, strip_pppoe
from bng_tpu.utils.net import ip_to_u32

NOW = 1_753_000_000
PUB = [ip_to_u32("198.18.0.1")]
SUB_BASE = ip_to_u32("10.16.0.0")
SUBS, PER_BLOCK = 64, 8
DSTS = [ip_to_u32("93.184.0.7") + i for i in range(3)]
MAC_A, MAC_B = bytes.fromhex("02aa00000001"), bytes.fromhex("02aabbccdd01")
FRAMINGS = ("plain", "vlan", "qinq", "pppoe", "qinq-pppoe", "options")
FLAGS = {"eim": FLAG_EIM, "napt": 0, "eim-parity": FLAG_EIM | FLAG_PORT_PARITY}


# -- the parent's create, one flow at a time (the reference) ------------------

def parent_handle_new_flow(nat, src_ip, dst_ip, src_port, dst_port, proto,
                           pkt_len, now):
    """`NATManager.handle_new_flow` as it stood before PR 54."""
    block = nat.blocks.get(src_ip)
    if block is None:
        return None
    if proto == 1:
        dst_port = 0
    skey = nat._key(src_ip, dst_ip, src_port, dst_port, proto)
    existing = nat.sessions.lookup(skey)
    if existing is not None:
        return int(existing[SV_NAT_IP]), int(existing[SV_NAT_PORT])
    if nat.flags & FLAG_EIM:
        got = nat._get_eim(src_ip, src_port, proto, block, now)
    else:
        p = nat._allocate_port(block, src_port, proto)
        got = (block["public_ip"], p) if p else None
    if got is None:
        nat._log(LOG_PORT_EXHAUSTION, block["subscriber_id"], src_ip,
                 block["public_ip"], src_port, 0, dst_ip, dst_port, proto, now)
        nat.exhausted["port"] += 1
        nat._exhaust_log.report(NATExhaustedError("full"), resource="port")
        return None
    nat_ip, nat_port = got
    row = np.zeros((SESSION_WORDS,), dtype=np.uint32)
    row[SV_NAT_IP], row[SV_NAT_PORT] = nat_ip, nat_port
    row[SV_ORIG_IP], row[SV_ORIG_PORT] = src_ip, src_port
    row[SV_DEST_IP], row[SV_DEST_PORT] = dst_ip, dst_port
    row[SV_CREATED] = row[SV_LAST_SEEN] = now
    row[SV_STATE], row[SV_PROTO] = NAT_STATE_NEW, proto
    row[SV_PKTS_OUT], row[SV_BYTES_OUT] = 1, pkt_len
    nat.sessions.insert(skey, row)
    rkey = nat._key(dst_ip, nat_ip, 0 if proto == 1 else dst_port, nat_port,
                    proto)
    rrow = np.zeros((REVERSE_WORDS,), dtype=np.uint32)
    rrow[:4] = skey
    nat.reverse.insert(rkey, rrow)
    nat._log(LOG_SESSION_CREATE, block["subscriber_id"], src_ip, nat_ip,
             src_port, nat_port, dst_ip, dst_port, proto, now, flags=0)
    return nat_ip, nat_port


def decoded_flow(frame: bytes, pppoe: bool):
    """What `NewFlows.create` read of a frame before PR 54."""
    view = strip_pppoe(frame) if pppoe else frame
    try:
        d = P.decode(view)
    except Exception:  # noqa: BLE001 — a cut frame
        return None
    if d.ethertype != 0x0800:
        return None
    return (d.src_ip, d.dst_ip, d.icmp_id if d.proto == 1 else d.src_port,
            0 if d.proto == 1 else d.dst_port, d.proto, len(view))


# -- frames -------------------------------------------------------------------

def framed(plain: bytes, framing: str, sid: int = 7) -> bytes:
    """A plain Ethernet + IPv4 frame under another framing."""
    macs, ip = plain[:12], plain[14:]
    tags = {"vlan": struct.pack("!HH", 0x8100, 100),
            "qinq": struct.pack("!HHHH", 0x88A8, 200, 0x8100, 100)}
    if framing == "options":  # a 24-byte header: IHL 6, one word of options
        ip = bytes([0x46]) + ip[1:20] + b"\x01\x01\x01\x00" + ip[20:]
    tag = tags.get(framing.split("-")[0], b"")
    if framing.endswith("pppoe"):
        return (macs + tag + struct.pack("!HBBHHH", 0x8864, 0x11, 0, sid,
                                         len(ip) + 2, 0x0021) + ip)
    return macs + tag + b"\x08\x00" + ip


def frame_of(rng, src, dst, sport, dport, proto) -> bytes:
    pay = bytes(int(rng.integers(0, 24)))
    if proto == 17:
        plain = P.udp_packet(MAC_A, MAC_B, src, dst, sport, dport, pay)
    elif proto == 6:
        plain = P.tcp_packet(MAC_A, MAC_B, src, dst, sport, dport, pay)
    elif proto == 1:
        plain = P.icmp_echo_packet(MAC_A, MAC_B, src, dst, sport, payload=pay)
    else:  # a protocol with no ports to read: GRE
        plain = (P.eth_header(MAC_B, MAC_A, 0x0800)
                 + P.ipv4_header(src, dst, 8, proto) + bytes(8))
    return framed(plain, FRAMINGS[int(rng.integers(len(FRAMINGS)))])


def twin(flags: int):
    """One provisioned manager: 64 subscribers with an 8-port block each,
    subscriber i holding i % 8 flows (so one in eight has one port left and
    one in eight none used), its log kept."""
    log = []
    nat = NATManager(public_ips=PUB, ports_per_subscriber=PER_BLOCK,
                     flags=flags, sessions_nbuckets=1 << 10,
                     sub_nat_nbuckets=1 << 8, log_sink=log.append)
    ips = (SUB_BASE + np.arange(SUBS)).astype(np.uint32)
    assert nat.bulk_allocate_nat(ips, NOW) == SUBS
    sub = np.repeat(np.arange(SUBS), np.arange(SUBS) % PER_BLOCK)
    k = np.arange(len(sub))
    _ip, _port, ok = nat.bulk_flows(
        ips[sub], np.full(len(k), DSTS[0], np.uint32),
        (41000 + k).astype(np.uint32), np.full(len(k), 443, np.uint32),
        np.where(k % 2 == 0, 17, 6).astype(np.uint32), pkt_len=64, now=NOW)
    assert bool(ok.all())
    held = [(int(ips[s]), DSTS[0], 41000 + int(i), 443, 17 if i % 2 == 0 else 6)
            for i, s in zip(k, sub)]
    return nat, log, held


def batch_of(rng, n: int, held: list) -> list[bytes]:
    """`n` seeded frames: new flows of UDP, TCP, ICMP and GRE from the 64
    subscribers and from two sources with no block, endpoints drawn from
    few enough values that internal endpoints repeat (EIM) and blocks run
    out, some flows whose session exists, a key twice, a block with one port
    left, a frame that is not IPv4 and two cut short, every framing among
    them."""
    frames = []
    for _ in range(n):
        src = SUB_BASE + int(rng.integers(0, SUBS + 2))
        proto = (17, 6, 1, 47)[int(rng.choice(4, p=[0.5, 0.3, 0.15, 0.05]))]
        frames.append(frame_of(rng, src, DSTS[int(rng.integers(3))],
                               5000 + int(rng.integers(0, 12)),
                               (53, 443)[int(rng.integers(2))], proto))
    for i in rng.choice(n, max(n // 8, 1), replace=False).tolist():
        frames[i] = frame_of(rng, *held[int(rng.integers(len(held)))])
    special = []
    if n >= 21:
        # a source with no block, and two new endpoints of the subscriber
        # whose block has one port left: the earlier lane gets it
        special = rng.choice(n, 6, replace=False).tolist()
        bare, last, full, *cut = special
        last, full = sorted((last, full))
        frames[bare] = frame_of(rng, SUB_BASE + SUBS, DSTS[1], 6000, 53, 17)
        frames[last] = frame_of(rng, SUB_BASE + 7, DSTS[1], 6001, 53, 17)
        frames[full] = frame_of(rng, SUB_BASE + 7, DSTS[1], 6002, 53, 17)
        frames[cut[0]] = frames[cut[0]][:30]  # inside the IPv4 header
        l4 = len(frames[cut[1]]) - len(flow_view(frames[cut[1]])) + 20
        frames[cut[1]] = frames[cut[1]][:l4 + 2]  # two bytes into the ports
        frames[cut[2]] = P.eth_header(MAC_B, MAC_A, 0x0806) + bytes(28)  # ARP
    if n >= 2:  # a key twice in one batch, a lane apart or many
        a, b = rng.choice([i for i in range(n) if i not in special], 2,
                          replace=False).tolist()
        plain = frames[a][:12] + b"\x08\x00" + flow_view(frames[a])
        frames[a], frames[b] = framed(plain, "qinq"), framed(plain, "vlan")
    return frames


def flow_view(frame: bytes) -> bytes:
    """The IPv4 packet of a frame `framed` built."""
    view = strip_pppoe(frame)
    off = 12
    while view[off:off + 2] in (b"\x81\x00", b"\x88\xa8"):
        off += 4
    return view[off + 2:]


# -- the comparison -----------------------------------------------------------

def contents(table) -> dict:
    used = np.nonzero(table.used)[0]
    return {tuple(k): tuple(v) for k, v in
            zip(table.keys[used].tolist(), table.vals[used].tolist())}


def shipped(table, upd) -> dict:
    """key -> value row for the slots an update batch ships."""
    idx = np.asarray(upd.idx)
    real = idx < table.S
    return {tuple(k): tuple(v) for k, v in
            zip(table.keys[idx[real]].tolist(),
                np.asarray(upd.vals)[real].tolist())}


def book(nat) -> dict:
    return {"eim": nat.eim, "ext": nat._ext_ports, "exhausted": nat.exhausted,
            "next": {ip: b["next_port"] for ip, b in nat.blocks.items()},
            "counts": (nat.sessions.count, nat.reverse.count)}


CASES = [pytest.param(n, kind, pppoe, id=f"{n}-{kind}-{'pppoe' if pppoe else 'ipoe'}")
         for n in (1, 2, 21, 65, 300) for kind in FLAGS
         for pppoe in ((True, False) if kind == "eim" else (True,))]


@pytest.mark.parametrize("n, kind, pppoe", CASES)
def test_a_batch_leaves_the_state_one_by_one_leaves(n, kind, pppoe):
    rng = np.random.default_rng([54, n, len(kind), pppoe])
    (batch, blog, held), (single, slog, _), (parent, plog, _) = (
        twin(FLAGS[kind]) for _ in range(3))
    frames = batch_of(rng, n, held)
    dev = batch.device_tables()  # clean from here: what follows is the batch's
    for nat in (single, parent):
        nat.device_tables()

    before = {name: set(contents(getattr(batch, name)))
              for name in ("sessions", "reverse")}
    errors, calls = [], []

    def create(*cols):  # the batch's one call, its answers kept
        calls.append(batch.handle_new_flows(*cols))
        return calls[-1]

    kept = NewFlows(create, bound=n).punt_many(
        frames, [1] * n, NOW + 5, pppoe,
        on_error=lambda i, e: errors.append((i, e)))
    flows = [decoded_flow(f, pppoe) for f in frames]
    assert flows == [flow_of(f, pppoe) for f in frames]
    assert len(calls) == (1 if any(f is not None for f in flows) else 0)
    answers = iter(calls[0] if calls else [])
    got = [None if f is None else next(answers) for f in flows]
    want = [None if f is None else single.handle_new_flow(*f, NOW + 5)
            for f in flows]
    ref = [None if f is None else parent_handle_new_flow(parent, *f, NOW + 5)
           for f in flows]
    assert got == want == ref
    assert kept == [g is not None for g in got] and not errors
    parsed = [f[:5] for f in flows if f is not None]
    assert n < 2 or len(set(parsed)) < len(parsed), "a key twice"
    if n >= 21:  # every kind of lane is in the batch
        assert any(f is None for f in flows) and None in [
            g for g, f in zip(got, flows) if f is not None]
        assert any(g is not None for g in got)

    for name in ("sessions", "reverse"):
        a, b, c = (getattr(m, name) for m in (batch, single, parent))
        assert contents(a) == contents(b) == contents(c), name
        keys = np.array(list(contents(c)), dtype=np.uint32)
        found, vals = a.lookup_many(keys)
        assert bool(found.all())
        for key, row in zip(keys, vals):  # the walk finds what the batch placed
            assert np.array_equal(a.lookup(key), row)
            assert np.array_equal(c.lookup(key), row)
        assert not a._dirty_all, "a live batch never asks for a full upload"
    assert book(batch) == book(single) == book(parent)
    assert blog == slog == plog
    assert ([e.event_type for e in blog].count(LOG_SESSION_CREATE)
            == len(contents(batch.sessions)) - len(before["sessions"]))

    # what crosses to the chip: the batch's rows, nothing left behind, and
    # the device answers for every flow opened
    opened = {f[:5]: g for f, g in zip(flows, got) if g is not None}
    ups = [m.make_updates() for m in (batch, single, parent)]
    assert batch.sessions.dirty_count() == batch.reverse.dirty_count() == 0
    skeys = np.array([batch._key(*f) for f in opened], dtype=np.uint32)
    for t in (0, 1):
        name = ("sessions", "reverse")[t]
        new = [shipped(getattr(m, name), u[t])
               for m, u in zip((batch, single, parent), ups)]
        fresh = set(contents(getattr(batch, name))) - before[name]
        assert all(set(s) >= fresh for s in new), name
        assert all({k: s[k] for k in fresh} == {k: new[0][k] for k in fresh}
                   for s in new), name
    if not opened:
        return
    dev = apply_nat_updates(dev, ups[0])
    geom = batch.geom.sessions
    res = device_lookup(dev.sessions, jnp.asarray(skeys), geom.nbuckets,
                        geom.stash)
    assert bool(res.found.all())
    assert [(int(r[SV_NAT_IP]), int(r[SV_NAT_PORT]))
            for r in np.asarray(res.vals)] == list(opened.values())


# -- the kick walk, a full table, and the counters ------------------------------

def _tiny(stash: int):
    """A manager whose session and reverse tables hold 8 ways and `stash`
    stash slots, one subscriber with a block of 64 ports."""
    log = []
    nat = NATManager(public_ips=PUB, ports_per_subscriber=64,
                     sessions_nbuckets=2, sub_nat_nbuckets=1 << 4,
                     stash=stash, log_sink=log.append)
    assert nat.allocate_nat(SUB_BASE, NOW) is not None
    log.clear()
    return nat, log


def _cols(n: int, port0: int = 7000):
    return ([SUB_BASE] * n, [DSTS[0]] * n, [port0 + i for i in range(n)],
            [443] * n, [17] * n, [64] * n)


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_creates_and_singles_are_counted(armed):
    from bng_tpu.telemetry import spans as tele

    nat, log = _tiny(stash=64)
    tr = tele.arm(tele.Tracer()) if armed else None
    try:
        got = nat.handle_new_flows(*_cols(12), NOW)
        again = nat.handle_new_flows(*_cols(12), NOW)  # all held: no create
    finally:
        tele.disarm()
    assert None not in got and got == again and len(log) == 12
    assert nat.sessions.count == nat.reverse.count == 12
    sums = (tr or tele.Tracer()).sums()
    # eight ways a table: at least four of the twelve walked and were stashed
    assert sums["newflow_creates"] == (1 if armed else 0)
    assert (4 <= sums["newflow_singles"] <= 12) if armed else (
        sums["newflow_singles"] == 0)
    assert int(nat.sessions.used[8:].sum()) == 4


def test_a_table_full_for_one_key_costs_that_flow_alone():
    """Twelve slots a table, fourteen flows: two get the table's error as
    their answer (no reverse row, no compliance record), twelve are created;
    `punt_many` reports the two with their lanes and hands back the rest, and
    the batch of one raises as `insert` does."""
    nat, log = _tiny(stash=4)
    errors = []
    nf = NewFlows(nat.handle_new_flows, bound=64)
    frames = [P.udp_packet(MAC_A, MAC_B, SUB_BASE, DSTS[0], 7000 + i, 443, b"")
              for i in range(14)]
    kept = nf.punt_many(frames, [1] * 14, NOW, False,
                        on_error=lambda i, e: errors.append((i, e)))
    assert kept.count(True) == 12 and len(nf) == 12
    assert [i for i, _e in errors] == [i for i, k in enumerate(kept) if not k]
    assert all(isinstance(e, RuntimeError) and "full" in str(e)
               for _i, e in errors)
    st = nf.stats
    assert (st.admitted, st.refused, st.hold_full) == (12, 0, 0)
    assert nat.sessions.count == nat.reverse.count == 12 == len(log)
    assert [fr for fr, _fl in nf.take(64)] == [f for f, k in zip(frames, kept)
                                               if k]
    with pytest.raises(RuntimeError, match="full"):
        nat.handle_new_flow(SUB_BASE, DSTS[1], 7100, 443, 17, 64, NOW)
    assert nat.sessions.count == 12  # rolled back: nothing lost
