"""The multi-ISP + lawful-intercept kit: a wholesale / open-access BNG.

The default kit's layout (IPoE subscribers behind CGNAT), and on top of it
what one box that serves several retail ISPs holds:

- `upstreams` ISP uplinks (`isp0`..), one gateway and one routing table
  each, equal weights. Every subscriber has a class by a seeded draw
  (residential 90%, business 8%, wholesale 2%); residential may leave by
  any table, business and wholesale by two of them each. Every subscriber
  is bound to its class's next hop (`route_rows` = every subscriber, as the
  source routes every session), so every upstream data frame leaves with
  the L2 destination of the gateway elected for its subscriber.
- `warrants` active warrants, each on one subscriber behind NAT (a target
  that sends nothing mirrors nothing), drawn from the seed. The first
  `filtered_warrants` of them carry one filter row: UDP to port 443.

Traffic is the default kit's mix, frame for frame.

The plain reference is `Plain`: from the layout alone (no table, no jax,
nothing `bng_tpu/edge/compile.py` chose) it recomputes a subscriber's next
hop (FNV-1a32 of the address's four wire-order bytes, modulo the summed
weights of the class's upstreams that are up, walked in name order) and
what a warrant takes of a frame. `Reference` holds an upstream data reply
to the default kit's reference AND its first six bytes to that MAC; a
downstream reply and a DHCP reply exactly to the default kit's. And it
holds the intercept sink (an exporter this kit registers with the app's
`InterceptManager`, which keeps every CC record) to what the traffic
pushed, all of it since the app was built: every data frame of a subscriber
under a warrant that passes the warrant's filter arrived once a push, byte
for byte as it was pushed (before NAT), under that warrant's id, and
nothing else did; and the device's two tap counters and the pump's read
what the same frames give under the device's rule. The device's rule is a
pre-filter (`edge/ops.py tap_match`: the filter row's port matches either
port of the frame), the manager's the exact one (`filter_dest_ports`): a
filtered warrant's downstream UDP frames from port 443 are mirrored by the
device and refused by the manager. A sink that differs is a kind of reply
the sample never holds (`kinds`), so `correct` is false by
`sample_kinds_missing`: the harness's `check` is not edited.

`stale-binding` here: after the upload one upstream (`isp1`) went down on
the host, and the recompile that should follow has not reached the device:
its route table is the one from before. The reference elects among the
upstreams that are left, so three residential subscribers in four, and
every second business one, leave with a gateway the reference does not
elect (`h % 4` against `h % 3`: modulo election moves more than the dead
upstream's own share), and `correct` is false by
`sampled_replies_differing`.
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np

from benchmark.kits import ipoe
from benchmark.lib.app import BenchError, shape
from benchmark.lib.gen import DOWN, UP

CLASSES = ("residential", "business", "wholesale")
CLASS_SHARE = (0.90, 0.08, 0.02)
TABLE_BASE = 101  # upstream i routes by table TABLE_BASE + i
FILTER_PORT, FILTER_PROTO = 443, 17  # a filtered warrant: UDP to port 443
STALE_DOWN = "isp1"  # the upstream the stale-binding control takes down


def stage_bytes(batch: int, slot: int) -> int:
    """Bytes the edge stage must move in one step, from shapes: two cuckoo
    probes a lane (two bucket rows of four ways x 8 words and one 8-word
    value row each: the tap table's and the route table's), the filter
    array once (64 rows x 4 words), six bytes of next-hop MAC written and
    one mirror word written a lane. `slot` is not in it: the rewrite
    patches the first six bytes of a frame in place."""
    del slot
    probe = 2 * 4 * 8 * 4 + 8 * 4
    return batch * (2 * probe + 6 + 4) + 64 * 4 * 4


def fnv1a32(data: bytes) -> int:
    """FNV-1a, 32 bits, written out: the reference shares no hash with the
    program."""
    h = 0x811C9DC5
    for b in data:
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return h


# --------------------------------------------------------------------------
# the plain reference for the edge stage
# --------------------------------------------------------------------------

class Plain:
    """What the deployment does at its edge, from its rules:

    1. an upstream data frame of a bound subscriber leaves for the gateway
       elected for it: of the upstreams that are up and whose table the
       subscriber's class may use (no entry: any), in name order, the one
       at which the running sum of weights passes FNV-1a32(address, four
       wire-order bytes) modulo the summed weights;
    2. a data frame of a subscriber under a warrant, either way, is taken
       if the warrant has no filter; with the filter (UDP to port P) the
       device passes on what is UDP with P as either port, and the
       manager keeps of that what has P as its destination port. DHCP is
       no data frame.

    `upstreams`: [(name, table, weight, gateway MAC)]; `class_tables`:
    {class: tables}; `down`: names of upstreams that are down;
    `warrants`: {subscriber address: (warrant id, filtered)}."""

    def __init__(self, upstreams, class_tables, warrants, down=()):
        self.upstreams = sorted(upstreams)
        self.class_tables = class_tables
        self.warrants = warrants
        self.down = set(down)

    def next_hop(self, sub_ip: int, klass: str) -> bytes | None:
        allowed = self.class_tables.get(klass)
        ups = [u for u in self.upstreams if u[0] not in self.down
               and (allowed is None or u[1] in allowed)]
        total = sum(max(1, u[2]) for u in ups)
        if not total:
            return None
        h = fnv1a32(int(sub_ip).to_bytes(4, "big")) % total
        acc = 0
        for _name, _table, weight, mac in ups:
            acc += max(1, weight)
            if h < acc:
                return mac
        return None

    def tap(self, sub_ip: int, proto: int, sport: int,
            dport: int) -> tuple[str | None, bool, bool]:
        """(warrant id or None, the device mirrors the frame, the sink
        gets it) for a data frame of the subscriber at `sub_ip`."""
        got = self.warrants.get(int(sub_ip))
        if got is None:
            return None, False, False
        wid, filtered = got
        if not filtered:
            return wid, True, True
        udp = proto == FILTER_PROTO
        return (wid, udp and FILTER_PORT in (sport, dport),
                udp and dport == FILTER_PORT)


# --------------------------------------------------------------------------
# layout and provisioning
# --------------------------------------------------------------------------

class Layout(ipoe.Layout):
    """The default layout, and who leaves by which ISP and who is tapped."""

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        s = config["sizes"]
        self.route_rows = int(s.get("route_rows", self.subscribers))
        if self.route_rows != self.subscribers:
            raise BenchError(f"route_rows {self.route_rows}: every one of the "
                             f"{self.subscribers} subscribers is routed in "
                             f"this deployment")
        self.n_upstreams = int(s.get("upstreams", 4))
        self.n_warrants = int(s.get("warrants",
                                    min(1024, self.nat_subscribers // 4)))
        self.n_filtered = int(s.get("filtered_warrants",
                                    min(64, self.n_warrants // 2)))
        if not (4 <= self.n_upstreams <= 250
                and 0 < self.n_filtered <= self.n_warrants
                <= self.nat_subscribers):
            raise BenchError(f"upstreams {self.n_upstreams}, warrants "
                             f"{self.n_warrants}, filtered {self.n_filtered}")
        # (name, table, weight, gateway MAC); the gateway's address beside
        self.upstreams = [(f"isp{i}", TABLE_BASE + i, 1,
                           bytes((0x02, 0xEE, 0, 0, 0x01, i)))
                          for i in range(self.n_upstreams)]
        self.gateways = [f"192.0.2.{i + 1}" for i in range(self.n_upstreams)]
        self.class_tables = {"business": (TABLE_BASE, TABLE_BASE + 1),
                             "wholesale": (TABLE_BASE + 2, TABLE_BASE + 3)}
        rng = np.random.default_rng([int(seed), 0xED6E])
        # class index (into CLASSES) of every subscriber
        self.klass = np.searchsorted(np.cumsum(CLASS_SHARE),
                                     rng.random(self.subscribers),
                                     side="right").clip(0, 2).astype(np.int8)
        # the tapped NAT subscribers, warrant k on the k-th; the first
        # `n_filtered` warrants carry the filter
        tapped = rng.choice(self.nat_subscribers, self.n_warrants,
                            replace=False)
        self.tapped_ips = self.sub_ips(self.nat_sub_index(tapped))
        self.warrants = {int(ip): (f"w-{k:04d}", k < self.n_filtered)
                         for k, ip in enumerate(self.tapped_ips.tolist())}

    def plain(self, stale: bool = False) -> Plain:
        return Plain(self.upstreams, self.class_tables, self.warrants,
                     down=(STALE_DOWN,) if stale else ())

    def class_of(self, sub: int) -> str:
        return CLASSES[int(self.klass[sub])]


class Sink:
    """The in-memory exporter: every CC record the manager delivers, as
    (warrant id, the frame); IRI records counted."""

    def __init__(self):
        self.cc: list[tuple[str, bytes]] = []
        self.iri = 0

    def deliver_cc(self, rec) -> None:
        self.cc.append((rec.warrant_id, bytes(rec.payload)))

    def deliver_iri(self, rec) -> None:
        self.iri += 1


def provision(app, lay: Layout, stale: bool = False) -> dict:
    """The default kit's tables through the same bulk writers; the
    upstreams, their next hops and every subscriber's binding through the
    routing manager and the app's `RouteProgram`; the warrants through the
    app's `InterceptManager` and `InterceptTapProgram`; one full upload."""
    from bng_tpu.control.intercept import (DeliveryMethod, Warrant,
                                           WarrantStatus)
    from bng_tpu.control.routing import LinkState, Upstream
    from bng_tpu.ops.antispoof import MODE_STRICT
    from bng_tpu.utils.net import u32_to_ip

    if shape(app) == "cluster":
        raise BenchError("the edge stage is not wired under --shards "
                         "(ROADMAP M9)")
    c = app.components
    if "route_program" not in c:
        raise BenchError("the app has no edge stage: the configuration's "
                         "argv lacks --edge-enabled")
    now = int(app.clock())
    took = {}
    idx = np.arange(lay.subscribers)
    macs, ips = lay.sub_macs(idx), lay.sub_ips(idx)
    t0 = time.time()
    c["fastpath"].add_subscribers_bulk(macs, pool_ids=1, ips=ips,
                                       lease_expiries=np.uint32(now + 86400))
    took["subscribers"] = time.time() - t0

    t0 = time.time()
    policy = c["policies"].get(app.config.default_policy)
    c["qos"].bulk_set_subscribers(ips, policy.download_bps, policy.upload_bps)
    c["antispoof"].bulk_add_bindings(macs, ips, MODE_STRICT)
    c["antispoof"].set_config(MODE_STRICT, log_violations=True)
    took["qos+antispoof"] = time.time() - t0

    t0 = time.time()
    j = np.arange(lay.nat_subscribers)
    made = c["nat"].bulk_allocate_nat(lay.sub_ips(lay.nat_sub_index(j)), now)
    if made != lay.nat_subscribers:
        raise BenchError(f"NAT blocks: {made} of {lay.nat_subscribers}")
    src, dst, sport, dport, proto = lay.flows(np.arange(lay.nat_flows))
    nat_ip, nat_port, ok = c["nat"].bulk_flows(src, dst, sport, dport, proto,
                                               pkt_len=64, now=now)
    if not bool(ok.all()):
        raise BenchError(f"NAT flows: {int(ok.sum())} of {len(ok)}")
    took["nat"] = time.time() - t0

    t0 = time.time()
    routes = c["route_program"]
    routes.class_tables = dict(lay.class_tables)
    for (name, table, weight, mac), gw in zip(lay.upstreams, lay.gateways):
        c["routing"].add_upstream(Upstream(
            name=name, gateway=gw, table=table, weight=weight,
            state=LinkState.UP))
        routes.set_neighbor(gw, mac)
    names = np.asarray(CLASSES, dtype=object)[lay.klass].tolist()
    bound = routes.bulk_bind(ips, names)
    if bound != lay.route_rows:
        raise BenchError(f"route rows: {bound} of {lay.route_rows}")
    took["routes"] = time.time() - t0

    t0 = time.time()
    sink = Sink()
    c["intercept"].add_exporter(DeliveryMethod.ETSI, sink)
    for ip, (wid, filtered) in lay.warrants.items():
        c["intercept"].add_warrant(Warrant(
            id=wid, liid=f"LI-{wid}", status=WarrantStatus.ACTIVE,
            target_ipv4=u32_to_ip(ip), valid_from=float(now - 1),
            valid_until=float(now + 365 * 86400),
            filter_protocols=[FILTER_PROTO] if filtered else [],
            filter_dest_ports=[FILTER_PORT] if filtered else []))
    armed = c["tap_program"].sync()
    if armed["rows"] != lay.n_warrants:
        raise BenchError(f"tap rows: {armed['rows']} of {lay.n_warrants}")
    took["warrants"] = time.time() - t0

    t0 = time.time()
    c["engine"].resync_tables()
    jax.block_until_ready(jax.tree_util.tree_leaves(c["engine"].tables))
    took["upload"] = time.time() - t0
    if stale:
        # the stale-binding control: the upstream is down on the host and
        # the recompile has not reached the device
        c["routing"].get_upstream(STALE_DOWN).state = LinkState.DOWN
    # `built`: every Traffic made for this app, the warm-up's too: the
    # sink holds what all of them pushed
    return {"took": took, "nat_ip": np.asarray(nat_ip, np.uint32),
            "nat_port": np.asarray(nat_port, np.uint32), "stale": stale,
            "sink": sink, "built": []}


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

class Traffic(ipoe.Traffic):
    """The default kit's mix, frame for frame."""

    def __init__(self, mix: dict, lay: Layout, prov: dict, app, seed: int,
                 seconds: float, stream: int = 0):
        super().__init__(mix, lay, prov, app, seed, seconds, stream)
        self.stale = bool(prov.get("stale"))
        self.sink = prov["sink"]
        self.built = prov["built"]
        self.built.append(self)

    def pushes(self) -> tuple[np.ndarray, np.ndarray]:
        """(frame ids, times the ring accepted each) over both streams: a
        flood's pool cycles in stream order, a fixed-rate frame is offered
        once."""
        times = []
        for s in self.streams:
            at, n = np.arange(len(s.ids)), max(len(s.ids), 1)
            times.append(s.sent // n + (at < s.sent % n) if self.flood
                         else (at < s.at).astype(np.int64))
        return (np.concatenate([s.ids for s in self.streams]),
                np.concatenate(times))


# --------------------------------------------------------------------------
# the reference a run is held to
# --------------------------------------------------------------------------

class Reference(ipoe.Reference):
    """DHCP and downstream data as the default kit; upstream data also to
    the elected gateway's MAC; the sink and the tap counters to what the
    traffic pushed."""

    def __init__(self, app, traffic: Traffic):
        super().__init__(app, traffic)
        self.plain = traffic.lay.plain(traffic.stale)
        self.sink_said, self.sink_ok = self.audit_sink(app, traffic)

    def audit_sink(self, app, traffic: Traffic) -> tuple[str, bool]:
        lay = traffic.lay
        want = collections.Counter()
        mirrored = filtered = 0
        for tr in traffic.built:
            ids, times = tr.pushes()
            keep = (times > 0) & ~tr.is_dhcp[ids]
            ids, times = ids[keep], times[keep]
            src, _dst, sport, dport, proto = lay.flows(tr.key[ids])
            for at in np.nonzero(np.isin(src, lay.tapped_ips))[0]:
                fid, n = int(ids[at]), int(times[at])
                ports = (int(sport[at]), int(dport[at]))
                if tr.kind[fid] == DOWN:  # as it arrives: from the peer
                    ports = (ports[1],
                             int.from_bytes(tr.frames[fid][36:38], "big"))
                wid, device, sink = self.plain.tap(int(src[at]),
                                                   int(proto[at]), *ports)
                mirrored += n * device
                filtered += n * (not device)
                if sink:
                    want[(wid, tr.frames[fid])] += n
        got = collections.Counter(traffic.sink.cc)
        stage = app.stats()["edge"]  # what `bng stats` prints of the stage
        counted, pump = stage["device"], stage["sink"]
        n = sum(want.values())
        facts = {"frames at the sink": (sum(got.values()), n),
                 "device mirrored": (counted["mirrored"], mirrored),
                 "device filtered": (counted["filtered"], filtered),
                 "pump mirrored": (pump["mirrored"], mirrored),
                 "pump delivered": (pump["cc_records"], n),
                 "pump dropped": (pump["dropped"], 0)}
        ok = got == want and all(a == b for a, b in facts.values())
        said = (f"intercept sink: {n} frames under "
                f"{len({w for w, _ in want})} warrants, each as it was "
                f"pushed, and no other"
                if ok else "intercept sink AS THE TRAFFIC PUSHED IT: "
                + ", ".join(f"{k} {a} (pushed: {b})"
                            for k, (a, b) in facts.items())
                + f", {sum(((got - want) + (want - got)).values())} records "
                  f"differ")
        return said, ok

    @property
    def kinds(self) -> dict:
        out = {True: "DHCP replies byte-for-byte",
               False: "data frames by mapping, payload, both checksums and, "
                      "upstream, the elected next hop's MAC"
                      + ("; " + self.sink_said if self.sink_ok else "")}
        if not self.sink_ok:
            out["sink"] = self.sink_said  # a kind no sample holds
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        tr = self.tr
        if not super().holds(fid, raw):
            return False
        if tr.kind[fid] != UP:
            return True
        sub = int(tr.key[fid]) // tr.lay.flows_per
        sub = int(tr.lay.nat_sub_index(sub))
        want = self.plain.next_hop(int(tr.lay.sub_ips([sub])[0]),
                                   tr.lay.class_of(sub))
        return want is not None and raw[:6] == want
