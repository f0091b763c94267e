"""The generator's tools: frames as byte matrices patched with numpy.

All frames are built during set-up (no codec call per frame): `data_frames`
and `dhcp_frames` make whole pools at once, `Stream` holds the frames that
enter on one side in offer order. Which frames a mix holds, and what each
carries, is the kit's `Traffic` (kits/ipoe.py is the default), which reads
the mix's data file and builds its pools with these.
"""

from __future__ import annotations

import numpy as np

DISCOVER, REQUEST, UP, DOWN = 0, 1, 2, 3
DATA_LEN = 60  # 64 on the wire with the FCS
_PAD = b"bng-benchmark-pad"


def mac_cols(mac_u64) -> np.ndarray:
    """[N] uint64 -> [N, 6] wire-order bytes."""
    return (np.asarray(mac_u64, np.uint64).astype(">u8").view(np.uint8)
            .reshape(-1, 8)[:, 2:])


def _be32(v) -> np.ndarray:
    return np.asarray(v, np.uint32).astype(">u4").view(np.uint8).reshape(-1, 4)


def _be16(v) -> np.ndarray:
    return np.asarray(v, np.uint16).astype(">u2").view(np.uint8).reshape(-1, 2)


def _csum(words_sum: np.ndarray) -> np.ndarray:
    s = words_sum.astype(np.uint64)
    s = (s & 0xFFFF) + (s >> 16)
    s = (s & 0xFFFF) + (s >> 16)
    return (~s & 0xFFFF).astype(np.uint16)


def _words(buf: np.ndarray) -> np.ndarray:
    """Sum of the big-endian 16-bit words of each row (even width)."""
    b = buf.astype(np.uint64)
    return (b[:, 0::2] * 256 + b[:, 1::2]).sum(axis=1)


def data_frames(src_mac, dst_mac, src_ip, dst_ip, sport, dport, proto,
                ids) -> np.ndarray:
    """[N, 60] uint8: Ethernet + IPv4 + UDP (18-byte payload) or TCP
    (PSH|ACK, 6-byte payload), valid IP and L4 checksums, id last."""
    n = len(ids)
    proto = np.asarray(proto, np.uint32)
    buf = np.zeros((n, DATA_LEN), np.uint8)
    buf[:, 0:6] = dst_mac
    buf[:, 6:12] = src_mac
    buf[:, 12:14] = (0x08, 0x00)
    total = DATA_LEN - 14
    buf[:, 14:16] = (0x45, 0)
    buf[:, 16:18] = _be16(np.full(n, total))
    buf[:, 22] = 64
    buf[:, 23] = proto
    buf[:, 26:30] = _be32(src_ip)
    buf[:, 30:34] = _be32(dst_ip)
    buf[:, 24:26] = _be16(_csum(_words(buf[:, 14:34])))
    buf[:, 34:36] = _be16(sport)
    buf[:, 36:38] = _be16(dport)
    udp = proto == 17
    seg_len = total - 20
    buf[udp, 38:40] = _be16(np.full(int(udp.sum()), seg_len))
    buf[~udp, 46] = 5 << 4
    buf[~udp, 47] = 0x18
    buf[~udp, 48:50] = (0xFF, 0xFF)
    pad = np.frombuffer(_PAD, np.uint8)
    buf[udp, 42:56] = pad[:14]
    buf[~udp, 54:56] = pad[:2]
    buf[:, 56:60] = _be32(ids)
    pseudo = (_words(buf[:, 26:34]) + proto.astype(np.uint64)
              + np.uint64(seg_len))
    c = _csum(_words(buf[:, 34:60]) + pseudo)
    c_udp = np.where(c == 0, 0xFFFF, c).astype(np.uint16)
    buf[udp, 40:42] = _be16(c_udp[udp])
    buf[~udp, 50:52] = _be16(c[~udp])
    return buf


def dhcp_frame(mac_u64: int, msg_type: int, xid: int,
               requested_ip: int = 0, server_id: int = 0) -> bytes:
    """One client frame through the codec (chip_smoke.dhcp_frame)."""
    from bng_tpu.control import dhcp_codec, packets

    mac = int(mac_u64).to_bytes(6, "big")
    p = dhcp_codec.build_request(mac, msg_type, xid=xid,
                                 requested_ip=requested_ip,
                                 server_id=server_id)
    p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
    return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                              p.encode().ljust(320, b"\x00"))


def _offsets(frame: bytes, needle: bytes) -> list[int]:
    out, at = [], frame.find(needle)
    while at >= 0:
        out.append(at)
        at = frame.find(needle, at + 1)
    return out


def dhcp_frames(macs, kinds, xids, req_ips, server_ip: int) -> np.ndarray:
    """[N, 362] uint8: the codec's DISCOVER / REQUEST with MAC, xid and
    requested address patched in. The offsets are found in the templates,
    not assumed."""
    from bng_tpu.control import dhcp_codec

    m_mac, m_xid, m_ip = 0x02F1F2F3F4F5, 0x7A7B7C7D, 0xC6D7E8F9
    n = len(xids)
    kinds = np.asarray(kinds)
    out = None
    for kind, msg in ((DISCOVER, dhcp_codec.DISCOVER),
                      (REQUEST, dhcp_codec.REQUEST)):
        rows = np.nonzero(kinds == kind)[0]
        req = kind == REQUEST
        tpl = dhcp_frame(m_mac, msg, m_xid, requested_ip=m_ip if req else 0,
                         server_id=server_ip if req else 0)
        if out is None:
            out = np.zeros((n, len(tpl)), np.uint8)
        if out.shape[1] != len(tpl):
            raise ValueError("DISCOVER and REQUEST templates differ in length")
        at_mac = _offsets(tpl, m_mac.to_bytes(6, "big"))
        at_xid = _offsets(tpl, m_xid.to_bytes(4, "big"))
        at_ip = _offsets(tpl, m_ip.to_bytes(4, "big"))
        if len(at_mac) != 2 or at_xid != [46] or len(at_ip) != (1 if req else 0):
            raise ValueError(f"unexpected DHCP template: {at_mac} {at_xid} {at_ip}")
        out[rows] = np.frombuffer(tpl, np.uint8)
        for at in at_mac:
            out[rows, at:at + 6] = mac_cols(macs[rows])
        out[rows, 46:50] = _be32(xids[rows])
        for at in at_ip:
            out[rows, at:at + 4] = _be32(req_ips[rows])
    return out


def row_bytes(buf: np.ndarray) -> list[bytes]:
    big, w = buf.tobytes(), buf.shape[1]
    return [big[i * w:(i + 1) * w] for i in range(len(buf))]


class Stream:
    """Frames entering on one side, in offer order."""

    def __init__(self, from_access: bool, ids, frames: list[bytes], due=None):
        self.from_access = from_access
        self.ids = np.asarray(ids, np.int64)
        self.frames = frames
        self.due = due  # seconds after the window opens; None = flood
        self.at = 0  # next frame to offer
        self.seen = 0  # fixed_rate: frames that have come due so far
        self.sent = 0  # frames the ring accepted (a flood pool cycles)
