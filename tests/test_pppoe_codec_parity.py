"""The device PPPoE stage against the host codec, on seeded random frames.

tests/test_pppoe_ops.py round-trips single frames; here a batch of frames a
seed draws (0 / 1 / 2 VLAN tags; good and bad ver/type, code and length;
a foreign MAC; an unknown session id; control protocols; discovery; frames
that fill the slot) goes through `pppoe_decap` / `pppoe_encap` / the QinQ
helpers once, and every lane is held to what `control/pppoe/codec.py` says
of that frame: whether it decaps, punts or is left alone, the bytes and the
length it leaves with, and the stats.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bng_tpu.control import packets
from bng_tpu.control.pppoe import codec
from bng_tpu.ops import pppoe as P
from bng_tpu.ops.parse import eth_vlan, parse_batch
from bng_tpu.runtime.tables import PPPoEFastPathTables

AC_MAC = bytes.fromhex("02aabbccdd01")
ROUTER = bytes.fromhex("02ee00000001")
L = 512
N_SESS = 48
KINDS = ("data", "data", "data", "bad-vertype", "bad-code", "short-length",
         "long-length", "foreign-mac", "unknown-id", "lcp", "ipv6", "discovery",
         "plain-ipv4", "fills-slot")


def sessions():
    sid = np.arange(1, N_SESS + 1, dtype=np.uint32) * 7
    mac = np.uint64(0x02C0FFEE0000) + np.arange(N_SESS, dtype=np.uint64)
    ip = np.uint32(0x0A100000) + np.arange(N_SESS, dtype=np.uint32) * 4
    fp = PPPoEFastPathTables(nbuckets=64, stash=8, server_mac=AC_MAC)
    fp.sessions_up_bulk(sid, mac, ip)
    return fp, sid, [int(m).to_bytes(6, "big") for m in mac], ip


def ip_packet(rng, src, dst, size):
    full = packets.udp_packet(b"\0" * 6, b"\0" * 6, int(src), int(dst),
                              int(rng.integers(1024, 65535)), 53,
                              bytes(rng.integers(0, 256, size, dtype=np.uint8)))
    return full[14:]


def upstream_frames(rng, n):
    """(frame, kind, session index) drawn by the seed."""
    _fp, sid, mac, ip = sessions()
    out = []
    for _ in range(n):
        kind = KINDS[int(rng.integers(len(KINDS)))]
        k = int(rng.integers(N_SESS))
        vlans = [None, [int(rng.integers(1, 4095))],
                 [int(rng.integers(1, 4095)), int(rng.integers(1, 4095))]][
            int(rng.integers(3))]
        room = L - 14 - 4 * len(vlans or []) - P.PPPOE_HDR - 28
        size = room if kind == "fills-slot" else int(rng.integers(0, 200))
        body = ip_packet(rng, ip[k], 0x08080808, size)
        proto = {"lcp": 0xC021, "ipv6": P.PPP_IPV6}.get(kind, P.PPP_IPV4)
        pkt = codec.PPPoEPacket(
            code=0x65 if kind == "bad-code" else 0,
            session_id=0xFFF0 if kind == "unknown-id" else int(sid[k]),
            payload=codec.ppp_frame(proto, body),
            ver_type=0x21 if kind == "bad-vertype" else 0x11).encode()
        if kind == "short-length":  # declares less than the PPP protocol word
            pkt = pkt[:4] + (1).to_bytes(2, "big") + pkt[6:]
        elif kind == "long-length":  # declares more than the frame holds
            pkt = pkt[:4] + (len(pkt)).to_bytes(2, "big") + pkt[6:]
        elif int(rng.integers(4)) == 0 and kind != "fills-slot":
            pkt += bytes(int(rng.integers(1, 9)))  # Ethernet padding
        src = bytes.fromhex("02dead00beef") if kind == "foreign-mac" else mac[k]
        if kind == "discovery":
            frame = codec.eth_frame(b"\xff" * 6, src, codec.ETH_PPPOE_DISCOVERY,
                                    bytes([0x11, 0x09, 0, 0, 0, 0]), vlans=vlans)
        elif kind == "plain-ipv4":
            frame = codec.eth_frame(AC_MAC, src, 0x0800, body, vlans=vlans)
        else:
            frame = codec.eth_frame(AC_MAC, src, codec.ETH_PPPOE_SESSION, pkt,
                                    vlans=vlans)
        out.append((frame, kind, k))
    return out


def host_decap(frame, by_id):
    """What the host stack says of one frame from the access side:
    ("decap", inner frame, session address) | ("punt", why) | ("pass",).
    `by_id`: session id -> (MAC, address)."""
    dst, src, et, payload, vlans = codec.parse_eth_vlan(frame)
    if et == codec.ETH_PPPOE_DISCOVERY:
        return ("punt", "ctrl")
    if et != codec.ETH_PPPOE_SESSION:
        return ("pass",)
    try:
        pkt = codec.PPPoEPacket.decode(payload)
        if pkt.code != codec.CODE_SESSION:
            raise ValueError("not a session frame")
        proto, body = codec.parse_ppp(pkt.payload)
    except ValueError:
        return ("punt", "bad")
    if proto != P.PPP_IPV4:
        return ("punt", "ctrl")
    if by_id.get(pkt.session_id, (None,))[0] != src:
        return ("punt", "miss")
    # codec.eth_frame names the tags by VID alone: keep the frame's own
    at = 12 + 4 * len(vlans)
    return ("decap", frame[:at] + b"\x08\x00" + body, by_id[pkt.session_id][1])


def batch(frames):
    pkt = np.zeros((len(frames), L), dtype=np.uint8)
    ln = np.zeros((len(frames),), dtype=np.uint32)
    for i, f in enumerate(frames):
        pkt[i, :len(f)] = np.frombuffer(f, dtype=np.uint8)
        ln[i] = len(f)
    return jnp.asarray(pkt), jnp.asarray(ln)


@pytest.mark.parametrize("seed", [11, 2**31 + 7, 3000000019])
def test_decap_agrees_with_the_codec_on_every_lane(seed):
    rng = np.random.default_rng([seed, 0xDECA])
    fp, sid, mac, ip = sessions()
    by_id = {int(s): (m, int(a)) for s, m, a in zip(sid, mac, ip)}
    drawn = upstream_frames(rng, 96)
    pkt, ln = batch([f for f, _k, _s in drawn])
    vo, et = eth_vlan(pkt)
    par = parse_batch(pkt, ln)
    assert (np.asarray(vo) == np.asarray(par.vlan_offset)).all()
    res = P.pppoe_decap(pkt, ln, vo, et, fp.by_sid.device_state(), fp.geom)
    out, out_len = np.asarray(res.out_pkt), np.asarray(res.out_len)
    counts = dict.fromkeys(("decap", "ctrl", "bad", "miss"), 0)
    for i, (frame, kind, _k) in enumerate(drawn):
        want = host_decap(frame, by_id)
        got = bytes(out[i, :int(out_len[i])])
        if want[0] == "decap":
            counts["decap"] += 1
            assert bool(res.done[i]) and not bool(res.punt[i]), kind
            assert got == want[1], kind
            assert int(res.src_ip_hint[i]) == want[2]
        else:
            assert not bool(res.done[i]), kind
            assert bool(res.punt[i]) == (want[0] == "punt"), kind
            assert got == frame, kind  # left as it came
            if want[0] == "punt":
                counts[want[1]] += 1
    stats = np.asarray(res.stats)
    assert counts["decap"] >= 10 and min(counts.values()) > 0, counts
    assert (int(stats[P.PST_DECAP]), int(stats[P.PST_CTRL_PUNT]),
            int(stats[P.PST_BAD]), int(stats[P.PST_MISS])) == (
        counts["decap"], counts["ctrl"], counts["bad"], counts["miss"])
    assert {"fills-slot"} <= {k for _f, k, _s in drawn}


@pytest.mark.parametrize("seed", [12, 2**31 + 8, 3000000020])
def test_encap_builds_the_frame_the_codec_builds(seed):
    """Downstream IPv4 to a session's address leaves in that session's
    framing, the AC's MAC as source; anything else is left alone, and so is
    a frame that would outgrow the slot (L - 8 bytes just fits)."""
    rng = np.random.default_rng([seed, 0xE2CA])
    fp, sid, mac, ip = sessions()
    drawn, frames = [], []
    for _ in range(96):
        k = int(rng.integers(N_SESS))
        vlans = [None, [int(rng.integers(1, 4095))],
                 [int(rng.integers(1, 4095)), int(rng.integers(1, 4095))]][
            int(rng.integers(3))]
        tags = 4 * len(vlans or [])
        kind = ("session", "session", "other-address", "fits-exactly",
                "too-long", "not-ipv4")[int(rng.integers(6))]
        size = {"fits-exactly": L - P.PPPOE_HDR - 14 - tags - 28,
                "too-long": L - P.PPPOE_HDR - 14 - tags - 28 + 1}.get(
            kind, int(rng.integers(0, 200)))
        dst = 0x0A100001 if kind == "other-address" else int(ip[k])
        body = ip_packet(rng, 0x08080808, dst, size)
        frame = codec.eth_frame(AC_MAC, ROUTER,
                                0x86DD if kind == "not-ipv4" else 0x0800, body,
                                vlans=vlans)
        want = None
        if kind in ("session", "fits-exactly"):
            framed = codec.PPPoEPacket(
                code=codec.CODE_SESSION, session_id=int(sid[k]),
                payload=codec.ppp_frame(P.PPP_IPV4, body)).encode()
            want = (mac[k] + AC_MAC + frame[12:12 + tags]
                    + codec.ETH_PPPOE_SESSION.to_bytes(2, "big") + framed)
            if not vlans:  # eth_frame names tags by VID: the frame keeps its own
                assert want == codec.eth_frame(
                    mac[k], AC_MAC, codec.ETH_PPPOE_SESSION, framed)
        drawn.append((kind, want))
        frames.append(frame)
    pkt, ln = batch(frames)
    par = parse_batch(pkt, ln)
    res = P.pppoe_encap(pkt, ln, par.vlan_offset, par.ethertype, par.dst_ip,
                        fp.by_ip.device_state(), fp.geom,
                        jnp.asarray(fp.server_mac))
    out, out_len = np.asarray(res.out_pkt), np.asarray(res.out_len)
    done = 0
    for i, (kind, want) in enumerate(drawn):
        got = bytes(out[i, :int(out_len[i])])
        assert bool(res.done[i]) == (want is not None), kind
        assert got == (want if want is not None else frames[i]), kind
        done += want is not None
    assert int(np.asarray(res.stats)[P.PST_ENCAP]) == done >= 10
    kinds = {k for k, _w in drawn}
    assert {"fits-exactly", "too-long", "other-address", "not-ipv4"} <= kinds
    assert max(int(x) for x in out_len) == L


@pytest.mark.parametrize("seed", [13, 2**31 + 9])
def test_qinq_push_and_pop_agree_with_the_codec(seed):
    rng = np.random.default_rng([seed, 0x0121])
    frames, s_tags, c_tags = [], [], []
    for _ in range(48):
        size = (L - 8 - 42, L - 8 - 42 + 1)[int(rng.integers(2))] \
            if int(rng.integers(6)) == 0 else int(rng.integers(0, 200))
        frames.append(packets.udp_packet(
            ROUTER, AC_MAC, 0x08080808, 0x0A100004, 53, 4000,
            bytes(rng.integers(0, 256, size, dtype=np.uint8))))
        s_tags.append(int(rng.integers(1, 4095)))
        c_tags.append(int(rng.integers(1, 4095)))
    pkt, ln = batch(frames)
    gate = jnp.asarray(rng.random(len(frames)) < 0.7)
    out, out_len, ok = P.qinq_push(pkt, ln, jnp.asarray(s_tags, jnp.uint32),
                                   jnp.asarray(c_tags, jnp.uint32), gate)
    tagged = []
    for i, f in enumerate(frames):
        fits = len(f) + 8 <= L
        assert bool(ok[i]) == (bool(gate[i]) and fits)
        want = (codec.eth_frame(f[0:6], f[6:12], 0x0800, f[14:],
                                vlans=[s_tags[i], c_tags[i]])
                if bool(ok[i]) else f)
        got = bytes(np.asarray(out)[i, :int(out_len[i])])
        assert got == want
        tagged.append(got)
    # and back: every tag off the gated lanes
    pkt2, ln2 = batch(tagged)
    vo, _et = eth_vlan(pkt2)
    out2, out_len2, ok2 = P.qinq_pop(pkt2, ln2, vo, gate)
    for i, f in enumerate(frames):
        got = bytes(np.asarray(out2)[i, :int(out_len2[i])])
        assert got == (f if bool(gate[i]) else tagged[i])
        assert bool(ok2[i]) == (bool(gate[i]) and len(tagged[i]) > len(f))


def test_no_stage_of_ops_pppoe_gathers_packet_bytes():
    """A per-lane index over the slot's width is a gather, which moves one
    byte an index: 1.0 GB/s on a v5e (PERF.md section 6, PR 26 and PR 32).
    The only gathers left in the four ops are the session tables' row
    probes (32-bit words); no `ui8` gather of any width."""
    fp, *_ = sessions()
    B, slot = 64, 1536
    pkt = jnp.zeros((B, slot), jnp.uint8)
    ln = jnp.full((B,), 100, jnp.uint32)
    gate = jnp.ones((B,), bool)
    tag = jnp.ones((B,), jnp.uint32)

    def every_op(by_sid, by_ip, pkt, ln):
        vo, et = eth_vlan(pkt)
        d = P.pppoe_decap(pkt, ln, vo, et, by_sid, fp.geom)
        e = P.pppoe_encap(pkt, ln, vo, et, tag, by_ip, fp.geom,
                          jnp.asarray(fp.server_mac))
        return (d.out_pkt, e.out_pkt, P.qinq_push(pkt, ln, tag, tag, gate)[0],
                P.qinq_pop(pkt, ln, vo, gate)[0])

    hlo = jax.jit(every_op).lower(fp.by_sid.device_state(),
                                  fp.by_ip.device_state(), pkt, ln
                                  ).compiler_ir(dialect="stablehlo")
    gathers = re.findall(r'"stablehlo\.gather"[^\n]*-> tensor<([0-9x]+)x(\w+)>',
                         str(hlo))
    assert gathers, "the pattern no longer finds the probes' gathers"
    assert all(ty == "ui32" for _dims, ty in gathers), gathers
    assert "stablehlo.while" not in str(hlo)
    assert "dynamic_slice" not in str(hlo)
