"""The churn kit: a CGNAT BNG serving the flows its subscribers open while it
forwards the ones they have.

The default kit's deployment (`kits/ipoe.py`: untagged IPoE subscribers, DHCP
bindings, QoS rows, strict antispoof bindings, a 1,024-port block and
established flows for each NAT subscriber), frame for frame, with, of a
flood's data frames, `new_flow_share_pct` (2) in a hundred the FIRST packet
of a flow that holds no session: an upstream frame from a provisioned NAT
subscriber (drawn without replacement: one new flow a subscriber a stream),
a fresh source port, a destination and protocol from the seed. The tables
cannot answer it, so `Traffic.to_host` is true for it and for nothing else.
As many again are a SECOND upstream packet of such a flow and as many the
downstream REPLY to its external endpoint, each at least
`follow_up_gap_frames` pool positions (both streams counted, as the loop
offers them) and at most twice that behind its first packet: by then the
session is on the chip, and a follow-up that reaches the host is a punt
nobody declared, which `run.py check` refuses.

`Layout` is the default kit's with the two keys (each with a default: the
tiny stand-ins replace `sizes` whole); `provision` is the default kit's, and
builds the plain reference from what it returned.

The plain reference is `Plain`: `kits/shardnat.py`'s (`struct`, plain Python
and numpy over the provisioned flows' mappings; nothing of `bng_tpu`) and,
for a flow that holds no session, **it allocates the mapping itself, before
the program does**, by the source's rule (bpf/nat44.c:408-528): the
subscriber's block is read off the subscriber's provisioned mappings, the
port is the next of the block in sequence from where provisioning stopped,
skipping ports in use at that protocol, wrapping once; the same internal
endpoint keeps its external endpoint (EIM, RFC 4787 REQ-1); no parity (the
program's default flags). That is how the downstream reply is addressed at
all: the frame is built before any punt is served. A frame the ring gave
back is held to the frame that was sent, byte for byte outside the
rewritten endpoint and the two checksum fields, both checksums verified by
a plain one's-complement sum: packet 1, the later upstream packet and the
reply alike.

At the end of a run `Reference` reads back, over every flow whose first
packet the ring accepted (the warm-up's too): the mapping back is
injective, inside the subscriber's block, and the host `NATManager` holds
the session and the reverse row with the reference's mapping (an
acknowledged write is read back). A finding there, a pool that wrapped, or
a sample without a first packet or without a reply over a session made in
the window, is a kind no sample holds: `sample_kinds_missing`, not correct.

What a further kit's author has to know (benchmark/README.md may not be
edited by the PR that adds a kit; PERF.md section 7 row 1 asks the next
`benchmark` PR to move this there):

- A flood whose declared frames must not repeat needs a pool that does not
  wrap: `run.py check` counts a cycled pool's declared frames once a cycle
  (`accepted`), and a first packet is new once. Size `pool_frames` for the
  fastest loop the cell may meet inside `run_seconds`, and refuse a run
  whose stream's `sent` passed its length (`Reference.wrapped`).
- A frame that re-enters the program (here: through the chip a second time)
  is kept out of the accepted count by the program, not by the kit:
  `Engine.stats.passed` counts the frame's first verdict only, the second
  pass adds to `fwd` or `dropped`, `ring.rx` does not see it, and it is
  popped once, so `lost_frames` closes at 0 without the kit's help.
- The warm-up stream (`stream=1`) draws its declared frames from
  subscribers the window's stream does not use, and holds no follow-up: a
  2,048-frame pool is shorter than any gap worth the name.

`stale-binding` is the default kit's: one subscriber in eight was
renumbered and the DHCP table that is uploaded is the one from before.
"""

from __future__ import annotations

import numpy as np

from benchmark.kits import ipoe, shardnat
from benchmark.kits.ipoe import (FLOW_PORT_BASE, REMOTE_PORT, ROUTER_MAC,
                                 SUB_IP_BASE)
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import (DISCOVER, DOWN, REQUEST, UP, Stream,
                               data_frames, dhcp_frames, mac_cols, row_bytes)

FIRST, SECOND, REPLY = 4, 5, 6  # frame kinds beside lib/gen.py's four
UPSTREAM = (UP, FIRST, SECOND)
UDP, TCP = shardnat.UDP, shardnat.TCP
# a follow-up is safe once every frame that could share a window with its
# first packet, or the window after, is behind it: the cap on frames
# outstanding (the ring's depth, 1,024) twice over
MIN_GAP = 2048
SESSION_BYTES, REVERSE_BYTES = 16 * 4, 8 * 4  # a row of each NAT table


def stage_bytes(batch: int, slot: int) -> int:
    """Bytes one apply call (`_apply_updates_jit`, PR 50's scatter) must
    write for the rows a step's new flows dirty, from shapes: the update
    batch holds 512 slots a table (`NATManager.update_slots`), each a
    4-word key, the row and a slot index, for the session table (16-word
    rows) and the reverse table (8-word rows); `batch` and `slot` are not in
    it: the apply program takes no packet. For the roofline share of the
    apply program that ROADMAP B0 (vii) still owes a reader for."""
    del batch, slot
    slots = 512
    return slots * ((4 * 4 + SESSION_BYTES + 4) + (4 * 4 + REVERSE_BYTES + 4))


class Layout(ipoe.Layout):
    """The default kit's, with the share of first packets and the gap."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        s = config["sizes"]
        self.new_flow_share = int(s.get("new_flow_share_pct", 2)) / 100.0
        self.gap = int(s.get("follow_up_gap_frames", 32768))
        # one order of the NAT subscribers a seed: the window's stream
        # opens flows from its head, the warm-up's from its tail
        self.openers = np.random.default_rng(
            [int(seed), 0xC4A]).permutation(self.nat_subscribers)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

class Plain(shardnat.Plain):
    """`kits/shardnat.py Plain` over the provisioned flows, and an allocator
    for the flows opened afterwards (`open`), by the source's rule."""

    def __init__(self, src, dst, sport, dport, proto, nat_ip, nat_port,
                 ports_per_block: int = 1024, port_lo: int = 1024):
        super().__init__(src, dst, sport, dport, proto, nat_ip, nat_port)
        self.per, self.lo = int(ports_per_block), int(port_lo)
        # a subscriber's block, off its provisioned mappings: the public
        # address they share, the block that holds their ports (block
        # start = lo + per * k), and the port after the last one given
        order = np.argsort(self.src, kind="stable")
        subs, at = np.unique(self.src[order], return_index=True)
        ports = self.nat_port[order].astype(np.int64)
        self.sub_ip = subs
        self.sub_pub = self.nat_ip[order][at]
        self.sub_start = self.lo + (np.minimum.reduceat(ports, at)
                                    - self.lo) // self.per * self.per
        self.sub_next = np.maximum.reduceat(ports, at) + 1
        if (np.maximum.reduceat(ports, at) >= self.sub_start + self.per).any():
            raise ValueError("a subscriber's provisioned ports span two blocks")
        # flows opened since: 5-tuple -> external endpoint; internal
        # endpoint -> external endpoint (EIM); and the way back
        self.opened: dict = {}
        self.opened_eim: dict = {}
        self.opened_back: dict = {}

    def block_of(self, src: int):
        """(row, public address, first port, last port) of a subscriber's
        block, or None for an address without provisioned mappings."""
        at = int(np.searchsorted(self.sub_ip, np.uint32(src)))
        if at == len(self.sub_ip) or int(self.sub_ip[at]) != src:
            return None
        start = int(self.sub_start[at])
        return at, int(self.sub_pub[at]), start, start + self.per - 1

    def in_use(self, ip: int, port: int, proto: int) -> bool:
        return (super().internal_of(ip, port, proto) is not None
                or (ip, port, proto) in self.opened_back)

    def eim_of(self, src: int, sport: int, proto: int):
        """The external endpoint an internal endpoint already maps to."""
        got = self.opened_eim.get((src, sport, proto))
        if got is not None:
            return got
        key = shardnat._endpoint(src, sport, proto)
        at = int(np.searchsorted(self.internal_sorted, key))
        if at < len(self.internal_sorted) and self.internal_sorted[at] == key:
            k = self.by_internal[at]
            return int(self.nat_ip[k]), int(self.nat_port[k])
        return None

    def open(self, src: int, dst: int, sport: int, dport: int, proto: int):
        """The external endpoint a flow that holds no session is given
        (nat44.c:469-528 `get_eim_mapping`, :408-466
        `allocate_port_from_block`), or None: no block, or a full one."""
        flow = (src, dst, sport, dport, proto)
        if flow in self.opened:
            return self.opened[flow]
        got = self.external_of(*flow) or self.eim_of(src, sport, proto)
        if got is None:
            block = self.block_of(src)
            if block is None:
                return None
            row, pub, start, end = block
            port = int(self.sub_next[row])
            for _ in range(self.per):
                if port > end:
                    port = start
                cand, port = port, port + 1
                if not self.in_use(pub, cand, proto):
                    self.sub_next[row] = port
                    got = (pub, cand)
                    break
            else:
                return None
        self.opened[flow] = got
        self.opened_eim[(src, sport, proto)] = got
        self.opened_back[(*got, proto)] = (src, sport)
        return got

    # one frame: the provisioned mappings, then the opened ones
    def external_of(self, src, dst, sport, dport, proto):
        return (super().external_of(src, dst, sport, dport, proto)
                or self.opened.get((src, dst, sport, dport, proto)))

    def internal_of(self, ip, port, proto):
        return (super().internal_of(ip, port, proto)
                or self.opened_back.get((ip, port, proto)))


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """The default kit's provisioning, and the plain reference over what
    it returned. `opened`: every new flow any stream of this run built,
    the warm-up's too, for the read-back at the end."""
    if shape(app) == "cluster":
        raise BenchError("the churn kit provisions one chip: the mesh's "
                         "loop holds a new flow's frame by shard and no "
                         "cell runs it yet")
    prov = ipoe.provision(app, lay, stale)
    nat = app.components["nat"]
    prov["plain"] = Plain(*lay.flows(np.arange(lay.nat_flows)),
                          prov["nat_ip"], prov["nat_port"],
                          ports_per_block=nat.ports_per_subscriber,
                          port_lo=nat.port_range[0])
    prov["built"] = []
    return prov


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def _merge(others: np.ndarray, *inserts) -> np.ndarray:
    """`others` in order, with each (ids, before) pair's ids inserted in
    front of `others[before]`: the inserted keep their distance, counted
    in frames of `others`, whatever else is inserted between them."""
    ids = np.concatenate([others, *(i for i, _b in inserts)])
    key = np.concatenate([np.arange(len(others), dtype=np.float64),
                          *(np.asarray(b, np.float64) - 0.5 + 0.1 * n
                            for n, (_i, b) in enumerate(inserts))])
    return ids[np.argsort(key, kind="stable")]


class Traffic(ipoe.Traffic):
    """A flood of the default kit's mix with new flows in it. `new` holds
    the flows this stream opens, a column each; `new_of[i]` is frame id
    i's flow among them (-1: a provisioned flow's, or DHCP)."""

    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        if mix["kind"] != "flood":
            raise ValueError("the churn kit builds floods: a fixed-rate mix "
                             "of new flows has no cell yet")
        self.mix, self.lay, self.flood, self.due = mix, lay, True, None
        self.plain, self.stream = prov["plain"], stream
        # every Traffic built over this provisioning, the warm-up's too:
        # the read-back at the end is over all of them
        self.built = prov["built"]
        rng = np.random.default_rng([int(seed), 0x7AF, stream])
        pool = int(mix["pool_frames"])
        n_dhcp = int(round(pool * mix["dhcp_share"]))
        n_data = pool - n_dhcp
        n_down = n_data // 2
        n_up = n_data - n_down
        self.n = n = n_dhcp + n_data

        # the new flows: one a NAT subscriber, the warm-up's (stream 1)
        # from the other end of the seed's order than the window's
        self.gap = min(lay.gap, pool // 8)
        follow = self.gap >= MIN_GAP
        want = int(round(n_data * lay.new_flow_share))
        warm = int(np.ceil(int(mix["warmup_frames"]) * lay.new_flow_share)) + 1
        room = (warm if stream == 1 else lay.nat_subscribers - warm)
        n_new = max(min(want, room, n_up // 2 if follow else n_up), 0)
        who = (lay.openers[len(lay.openers) - n_new:] if stream == 1
               else lay.openers[:n_new])
        sub = lay.nat_sub_index(who)
        self.new = {
            "sub": sub, "src": lay.sub_ips(sub),
            "dst": (lay.remote_base
                    + rng.integers(0, 1 << 16, n_new)).astype(np.uint32),
            # no provisioned flow of the subscriber leaves this port
            "sport": (FLOW_PORT_BASE + lay.flows_per
                      + rng.integers(0, 20000, n_new)).astype(np.uint32),
            "dport": np.full(n_new, REMOTE_PORT, np.uint32),
            "proto": np.where(rng.random(n_new) < 0.5, UDP, TCP
                              ).astype(np.uint32)}
        n_follow = n_new if follow else 0
        old_up, old_down = n_up - n_new - n_follow, n_down - n_follow

        ids = np.arange(n)
        self.kind = np.empty(n, np.int8)
        self.key = np.full(n, -1, np.int64)
        self.new_of = np.full(n, -1, np.int64)
        renew = rng.random(n_dhcp) < mix["renewal_ratio"]
        self.kind[:n_dhcp] = np.where(renew, REQUEST, DISCOVER)
        self.key[:n_dhcp] = (rng.choice(lay.subscribers, n_dhcp, replace=False)
                             if n_dhcp <= lay.subscribers
                             else rng.integers(0, lay.subscribers, n_dhcp))
        flow_up = rng.integers(0, lay.nat_flows, old_up)
        flow_down = np.concatenate(
            [flow_up, rng.integers(0, lay.nat_flows, max(old_down - old_up, 0))]
        )[:old_down]
        at = n_dhcp
        self.ids_of = {}
        for kind, count, keys in ((UP, old_up, flow_up), (FIRST, n_new, None),
                                  (SECOND, n_follow, None),
                                  (DOWN, old_down, flow_down),
                                  (REPLY, n_follow, None)):
            span = slice(at, at + count)
            self.kind[span] = kind
            if keys is None:
                self.new_of[span] = np.arange(count)
            else:
                self.key[span] = keys
            self.ids_of[kind] = ids[span]
            at += count
        self.xid_base = lay.xid_base
        self.is_dhcp = self.kind <= REQUEST
        self.to_host = self.kind == FIRST

        # the reference allocates, before any frame is offered
        ext = [self.plain.open(*(int(self.new[c][k]) for c in
                                 ("src", "dst", "sport", "dport", "proto")))
               for k in range(n_new)]
        if None in ext:
            raise BenchError("the reference's allocator refused a new flow: "
                             "a provisioned block is full")
        self.new["nat_ip"] = np.asarray([e[0] for e in ext], np.uint32)
        self.new["nat_port"] = np.asarray([e[1] for e in ext], np.uint32)
        self.frames = self.build(ids, n_dhcp, flow_up, flow_down, prov, app)

        acc, net = self.order(rng, ids[:n_dhcp], follow)
        self.streams = [
            Stream(True, acc, [self.frames[i] for i in acc], None),
            Stream(False, net, [self.frames[i] for i in net], None)]
        self.built.append(self)

    def build(self, ids, n_dhcp: int, flow_up, flow_down, prov: dict,
              app) -> list[bytes]:
        """The bytes of every frame id: the default kit's for DHCP and the
        provisioned flows; a new flow's upstream frames from its internal
        endpoint, its reply to the external endpoint the reference gave."""
        from bng_tpu.utils.net import ip_to_u32, parse_mac

        lay, new = self.lay, self.new
        server_mac = np.frombuffer(parse_mac(app.config.server_mac), np.uint8)
        router_mac = np.frombuffer(ROUTER_MAC, np.uint8)
        d = slice(0, n_dhcp)
        rows = [dhcp_frames(lay.sub_macs(self.key[d]), self.kind[d],
                            (ids[d] + self.xid_base).astype(np.uint32),
                            lay.sub_ips(self.key[d]),
                            ip_to_u32(app.config.server_ip))]

        def up(src, dst, sport, dport, proto, of):
            mac = mac_cols(lay.sub_macs(src.astype(np.int64) - SUB_IP_BASE))
            return data_frames(mac, server_mac, src, dst, sport, dport, proto,
                               of)

        def down(dst, dport, nat_ip, nat_port, proto, of):
            return data_frames(router_mac, server_mac, dst, nat_ip, dport,
                               nat_port, proto, of)

        cols = [new[c] for c in ("src", "dst", "sport", "dport", "proto")]
        rows.append(up(*lay.flows(flow_up), self.ids_of[UP]))
        rows.append(up(*cols, self.ids_of[FIRST]))
        k2 = self.new_of[self.ids_of[SECOND]]
        rows.append(up(*(c[k2] for c in cols), self.ids_of[SECOND]))
        _src, dst, _sport, dport, proto = lay.flows(flow_down)
        rows.append(down(dst, dport, prov["nat_ip"][flow_down],
                         prov["nat_port"][flow_down], proto,
                         self.ids_of[DOWN]))
        kr = self.new_of[self.ids_of[REPLY]]
        rows.append(down(new["dst"][kr], new["dport"][kr], new["nat_ip"][kr],
                         new["nat_port"][kr], new["proto"][kr],
                         self.ids_of[REPLY]))
        return [f for r in rows for f in row_bytes(r)]

    def order(self, rng, dhcp_ids, follow: bool):
        """The offer order of both streams. The loop tops the ring up from
        both at once, each by its share of the pool, so a frame's place in
        the pool is its place in its stream over that stream's share. A
        first packet lies anywhere in the first part of the access stream;
        its second packet and its reply follow it by one to two gaps."""
        of = self.ids_of
        a_others = rng.permutation(np.concatenate([dhcp_ids, of[UP]]))
        n_others = rng.permutation(of[DOWN])
        n_new = len(of[FIRST])
        if not n_new:
            return a_others, n_others
        A = len(a_others) + n_new + len(of[SECOND])
        N = len(n_others) + len(of[REPLY])
        # a gap in frames of each stream's `others`: what is inserted
        # between two frames only moves them further apart
        g_acc = int(np.ceil(self.gap * A / (A + N))) + 1
        g_net = int(np.ceil(self.gap * N / (A + N))) + 1
        # where the openers run out before the pool does (a rehearsal's
        # 128 NAT subscribers), the flows are opened at the mix's own
        # density from the head of the pool, not thinned over all of it
        want = max(int(round((self.n - len(dhcp_ids))
                             * self.lay.new_flow_share)), n_new)
        span = int(np.ceil(len(a_others) * n_new / want))
        if not follow:
            first_at = rng.integers(0, span + 1, n_new)
            return _merge(a_others, (of[FIRST], first_at)), n_others
        last = min(len(a_others) - 2 * g_acc, span,
                   int((len(n_others) - 2 * g_net) * A / N) - 2 * n_new - 1)
        if last < 1:
            raise BenchError("the pool is too short for the gap")
        first_at = rng.integers(0, last, n_new)
        second_at = first_at + g_acc + rng.integers(0, g_acc, n_new)
        acc = _merge(a_others, (of[FIRST], first_at), (of[SECOND], second_at))
        # the reply, by where the first packet came to lie in the pool
        where = np.empty(self.n, np.int64)
        where[acc] = np.arange(len(acc))
        first_pos = where[of[FIRST]]
        reply_at = (np.ceil((first_pos + 1) * N / A).astype(np.int64) + g_net
                    + rng.integers(0, g_net, n_new))
        net = _merge(n_others, (of[REPLY], reply_at))
        where[net] = np.arange(len(net))
        # held, not assumed: both follow-ups at least the gap behind
        lag2 = (where[of[SECOND]] - first_pos) * (A + N) / A
        lag_r = (where[of[REPLY]] / N - first_pos / A) * (A + N)
        if min(lag2.min(), lag_r.min()) < self.gap:
            raise BenchError(f"a follow-up lies {min(lag2.min(), lag_r.min())}"
                             f" pool positions behind its first packet, "
                             f"under the gap of {self.gap}")
        return acc, net

    def sent_once(self, kind: int) -> np.ndarray:
        """Of this stream's frames of `kind`, which the ring accepted."""
        sent = np.zeros(self.n, bool)
        for s in self.streams:
            sent[s.ids[:min(s.sent, len(s.ids))]] = True
        return sent[self.ids_of[kind]]

    def wrapped(self) -> bool:
        return any(s.sent > len(s.frames) for s in self.streams)


# --------------------------------------------------------------------------
# the reference a run is held to
# --------------------------------------------------------------------------

class Reference(ipoe.Reference):
    """DHCP as the default kit (byte for byte a host-only `DHCPServer`'s);
    a data frame is `Plain`'s, a new flow's by the mapping `Plain` gave it
    before the run; and the read-back over every flow opened."""

    # the kinds a sample must hold beside DHCP and data: kind -> (the key
    # `kinds` carries while the sample lacks it, what the check says of it)
    NEW = {FIRST: ("none-first", "first packets of new flows"),
           REPLY: ("none-reply",
                   "replies downstream over sessions made in the window")}

    def __init__(self, app, traffic: Traffic):
        super().__init__(app, traffic)
        self.seen = dict.fromkeys(self.NEW, 0)
        self.said, self.sound = self.read_back(app, traffic)

    @staticmethod
    def read_back(app, traffic: Traffic) -> tuple[str, bool]:
        """Over every flow whose first packet the ring accepted, in every
        stream built for this app: the pool did not wrap, the mapping back
        is injective and inside the subscriber's block, and the host
        NATManager holds the session and the reverse row."""
        from bng_tpu.ops.nat44 import SV_NAT_IP, SV_NAT_PORT

        plain, nat = traffic.plain, app.components["nat"]
        faults, n, back = [], 0, {}
        for tr in traffic.built:
            if tr.wrapped():
                faults.append(f"stream {tr.stream}'s pool wrapped: a first "
                              f"packet is new once (lengthen pool_frames)")
            new = tr.new
            for k in np.nonzero(tr.sent_once(FIRST))[0]:
                flow = tuple(int(new[c][k]) for c in
                             ("src", "dst", "sport", "dport", "proto"))
                src, dst, sport, dport, proto = flow
                ext = (int(new["nat_ip"][k]), int(new["nat_port"][k]))
                n += 1
                _row, pub, start, end = plain.block_of(src)
                if ext[0] != pub or not start <= ext[1] <= end:
                    faults.append(f"flow {flow} maps outside its block")
                if back.setdefault((*ext, proto), (src, sport)) != (src, sport):
                    faults.append(f"{ext} maps back to two internal endpoints")
                row = nat.sessions.lookup(
                    [src, dst, (sport << 16) | dport, proto])
                held = (None if row is None
                        else (int(row[SV_NAT_IP]), int(row[SV_NAT_PORT])))
                rev = nat.reverse.lookup(
                    [dst, ext[0], (dport << 16) | ext[1], proto])
                if held != ext or rev is None or [int(x) for x in rev[:4]] != [
                        src, dst, (sport << 16) | dport, proto]:
                    faults.append(f"flow {flow}: the reference gave {ext}, "
                                  f"the host's session table holds {held}, "
                                  f"reverse row {rev is not None}")
        if faults:
            return (f"READ-BACK over {n} flows opened: {len(faults)} faults, "
                    f"the first: {faults[0]}"), False
        return (f"{n} flows opened, each read back from the host's session "
                f"and reverse tables, inside its block, no endpoint twice"), True

    @property
    def kinds(self) -> dict:
        """DHCP, data, and a kind for each family of new-flow frames the
        sample has not held yet; a read-back that found a fault is a kind
        no sample holds."""
        out = {True: "DHCP replies byte-for-byte",
               False: f"data frames byte-for-byte outside the rewritten "
                      f"endpoint, both checksums verified "
                      f"({self.seen[FIRST]} first packets of new flows, "
                      f"{self.seen[REPLY]} replies over sessions made in the "
                      f"window among them; {self.said})"}
        out.update({key: "of the " + what
                    for k, (key, what) in self.NEW.items() if not self.seen[k]})
        if not self.sound:
            out["read-back"] = self.said
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        tr = self.tr
        if tr.is_dhcp[fid]:
            return super().holds(fid, raw)
        kind = int(tr.kind[fid])
        good = tr.plain.holds(tr.frames[fid], raw, kind in UPSTREAM)
        if good and kind in self.seen:
            self.seen[kind] += 1
        return good
