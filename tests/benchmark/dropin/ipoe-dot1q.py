"""A kit as a later PR would add it: the default kit's IPoE subscribers
behind one 802.1Q tag on the access side, which the program serves today
(`ops/parse.py` reads past the tag, the DHCP compose puts it back on the
reply, the NAT rewrite leaves it where it is).

tests/benchmark drops this file into `kits/` of a temporary copy of the
benchmark, beside a configuration that names it; no harness code changes.
What it shares with the default kit it imports; what differs is the
framing and what the reference says of it.
"""

from benchmark.kits import ipoe
from benchmark.kits.ipoe import Layout, provision  # noqa: F401  (the kit's names)

TAG = bytes([0x81, 0x00, 0x00, 100])  # TPID 802.1Q, VID 100


def untag(raw: bytes) -> bytes:
    return raw[:12] + raw[16:] if raw[12:16] == TAG else raw


class Traffic(ipoe.Traffic):
    def build_frames(self, ids, n_dhcp, flow_up, flow_down, prov, app):
        frames = super().build_frames(ids, n_dhcp, flow_up, flow_down, prov, app)
        n_access = n_dhcp + len(flow_up)
        return ([f[:12] + TAG + f[12:] for f in frames[:n_access]]
                + frames[n_access:])

    def reply_id(self, raw: bytes):
        return super().reply_id(untag(raw))

    def expected_data(self, i: int, app):
        want = super().expected_data(i, app)
        if want is not None and self.kind[i] == ipoe.UP:
            # the payload sits four bytes further into a tagged frame
            at = len(TAG) + (42 if want[4] == 17 else 54)
            want = want[:5] + (self.frames[i][at:],)
        return want


class Reference(ipoe.Reference):
    """The host DHCPServer reads the tag off the request and builds its
    reply with it; a translated upstream frame keeps the tag it came with;
    a downstream frame has none."""

    kinds = {True: "DHCP replies byte-for-byte, the access tag back on",
             False: "data frames by mapping, payload, both checksums and tag"}

    def holds(self, fid: int, raw: bytes) -> bool:
        tagged = raw[12:16] == TAG
        if tagged != (self.tr.kind[fid] != ipoe.DOWN):
            return False
        # DHCP: the tagged bytes whole; data: the default kit's comparison
        # on the frame inside the tag
        return super().holds(fid, raw if self.tr.is_dhcp[fid] else untag(raw))
