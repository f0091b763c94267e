"""A kit whose traffic the tables cannot answer whole: the default kit with
one DHCP frame in 16 a DISCOVER from a MAC it did not provision, as the
reference's own load harness sends first contacts among renewals
(`test/load/dhcp_benchmark.go`: unique MACs, 80% renewals).

The device's responder misses a stranger, the frame leaves with the verdict
PASS, the host's `DHCPServer` leases an address from the pool and its OFFER
goes out on the TX ring. The kit says so beforehand: `Traffic.to_host` is
true for the strangers' frame ids, and `run.py check` balances the slow
path's count, the program's passes and the responder's hits against the
declared frames that the ring accepted. Nothing else differs from the
default kit; tests/benchmark drops this file into `kits/` of a temporary
copy beside a configuration that names it.
"""

import numpy as np

from benchmark.kits import ipoe
from benchmark.kits.ipoe import Layout, provision  # noqa: F401  (the kit's names)
from benchmark.lib.gen import DISCOVER

EVERY = 16  # of the DHCP frames, by frame id


class Traffic(ipoe.Traffic):
    def build_frames(self, ids, n_dhcp, flow_up, flow_down, prov, app):
        # stranger j has the MAC one past the last provisioned one, plus j:
        # `Layout.sub_macs` of a key the tables do not hold
        who = np.arange(0, n_dhcp, EVERY)
        self.kind[who] = DISCOVER
        self.key[who] = self.lay.subscribers + np.arange(len(who))
        self.to_host = np.zeros(self.n, bool)
        self.to_host[who] = True
        return super().build_frames(ids, n_dhcp, flow_up, flow_down, prov, app)


class Reference(ipoe.Reference):
    """A stranger's OFFER is the host's to make: the address is the one the
    app's own `DHCPServer` holds on offer for that MAC (host state, nothing
    the device computed), inside the pool, no provisioned subscriber's and
    no other stranger's; the reply is then a host-only `DHCPServer`'s for
    that binding, byte for byte, like every other DHCP reply."""

    def __init__(self, app, traffic):
        super().__init__(app, traffic)
        self.offered = {}  # address -> the stranger's MAC it was offered to

    @property
    def kinds(self) -> dict:
        out = dict(ipoe.Reference.kinds)
        if self.offered:
            out[True] += f", {len(self.offered)} strangers' OFFERs among them"
        else:
            out["strangers"] = "OFFERs to strangers"  # a kind not held yet
        return out

    def holds(self, fid: int, raw: bytes) -> bool:
        tr, lay = self.tr, self.tr.lay
        if not tr.to_host[fid]:
            return super().holds(fid, raw)
        mac = lay.mac_base + int(tr.key[fid])
        server = self.app.components["dhcp"]
        ip, pool_id = server._offers.get(mac, (None, None))
        if ip is None or not server.pools.pools[pool_id].contains(ip):
            return False
        first = int(lay.sub_ips([0])[0])
        if first <= ip < first + lay.subscribers:
            return False
        if self.offered.setdefault(ip, mac) != mac:
            return False
        want = self.dhcp.reply(tr.frames[fid], mac, ip)
        return want is not None and raw == want
