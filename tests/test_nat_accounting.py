"""`nat44_update_sessions` against a numpy reference written here.

The accounting pass writes `sessions.vals` by whole-row scatters (PERF.md
section 6, PR 29). What it must keep, word for word over the whole array:
counters wrap mod 2**32, a lane that is not kept or not a hit touches
nothing, an upstream and a downstream lane of one flow land on one slot in
one batch, `SV_LAST_SEEN` is set (not raised), `SV_STATE` is the max of the
table's value and every TCP-ingress lane's new state, every other word
stays as it was.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bng_tpu.ops.nat44 import (NAT_STATE_CLOSING, NAT_STATE_ESTABLISHED,
                               NAT_STATE_NEW, SESSION_WORDS, SV_BYTES_IN,
                               SV_BYTES_OUT, SV_LAST_SEEN, SV_PKTS_IN,
                               SV_PKTS_OUT, SV_STATE, NATResult,
                               nat44_update_sessions)
from bng_tpu.ops.parse import Parsed
from bng_tpu.ops.table import TableState

U32 = np.uint32
FIN, RST, ACK, SYN = 0x01, 0x04, 0x10, 0x02
NOW = 50_000


def reference(vals, b, now_s):
    """np.add.at on uint32, np.maximum.at, plain assignment of now_s."""
    v = vals.copy()
    eh, ih = b["egress_hit"] & b["keep"], b["ingress_hit"] & b["keep"]
    hit = eh | ih
    slot = np.where(eh, b["e_slot"], b["i_slot"])[hit]
    plen = b["length"].astype(U32)
    zero = np.zeros_like(plen)
    for word, lanes, amount in ((SV_PKTS_OUT, eh, np.ones_like(plen)),
                                (SV_PKTS_IN, ih, np.ones_like(plen)),
                                (SV_BYTES_OUT, eh, plen),
                                (SV_BYTES_IN, ih, plen)):
        np.add.at(v, (slot, word), np.where(lanes, amount, zero)[hit])
    v[slot, SV_LAST_SEEN] = U32(now_s)
    flags, cur = b["tcp_flags"], b["i_state"]
    new_state = np.where(
        (flags & (FIN | RST)) != 0, NAT_STATE_CLOSING,
        np.where((cur == NAT_STATE_NEW) & ((flags & ACK) != 0),
                 NAT_STATE_ESTABLISHED, cur)).astype(U32)
    st = ih & b["is_tcp"]
    np.maximum.at(v, (b["i_slot"][st], SV_STATE), new_state[st])
    return v


def blank(rng, B, S):
    """A table of random rows and a batch in which no lane hits: every
    lane carries a slot inside the table and junk besides."""
    vals = rng.integers(0, 2**32, size=(S, SESSION_WORDS),
                        dtype=np.uint64).astype(U32)
    vals[:, SV_STATE] = rng.integers(0, 3, size=S)
    junk = rng.integers(0, S, size=B).astype(np.int32)
    b = dict(egress_hit=np.zeros(B, bool), ingress_hit=np.zeros(B, bool),
             keep=np.ones(B, bool), e_slot=junk.copy(), i_slot=junk[::-1].copy(),
             i_state=rng.integers(0, 5, size=B).astype(U32),
             tcp_flags=rng.choice(np.array([ACK, ACK | FIN, RST, SYN], U32), B),
             is_tcp=rng.random(B) < 0.5,
             length=rng.integers(60, 1500, size=B).astype(U32))
    return vals, b


def hit(vals, b, lanes, slots, up):
    """Make `lanes` hit `slots`, upstream (egress) or downstream."""
    b["egress_hit" if up else "ingress_hit"][lanes] = True
    b["e_slot" if up else "i_slot"][lanes] = slots
    if not up:  # what the kernel read for an ingress row
        b["i_state"][lanes] = vals[slots, SV_STATE]


def case_one_slot(rng, B, S):
    vals, b = blank(rng, B, S)
    lanes = np.arange(B)
    hit(vals, b, lanes[::2], 5, up=True)
    hit(vals, b, lanes[1::2], 5, up=False)
    return vals, b, NOW


def case_up_and_down_of_one_flow(rng, B, S):
    vals, b = blank(rng, B, S)
    n = min(B // 2, S)
    slots = rng.permutation(S)[:n]
    lanes = rng.permutation(B)
    hit(vals, b, lanes[:n], slots, up=True)
    hit(vals, b, lanes[n:2 * n], slots, up=False)
    return vals, b, NOW


def case_hit_but_not_kept(rng, B, S):
    vals, b, now = case_up_and_down_of_one_flow(rng, B, S)
    b["keep"] = rng.random(B) < 0.5
    return vals, b, now


def case_no_lane_hits(rng, B, S):
    vals, b = blank(rng, B, S)
    return vals, b, NOW


def case_counters_wrap(rng, B, S):
    vals, b, now = case_up_and_down_of_one_flow(rng, B, S)
    vals[:, SV_PKTS_OUT:SV_BYTES_IN + 1] = 0xFFFFFFFF
    # and one slot whose lanes alone carry the sum past 2**32
    lanes = np.arange(8)
    b["egress_hit"][lanes] = b["ingress_hit"][lanes] = False
    hit(vals, b, lanes, 3, up=True)
    vals[3, SV_BYTES_OUT] = U32(2**32 - 100)
    return vals, b, now


def case_clock_steps_back(rng, B, S):
    vals, b, _ = case_up_and_down_of_one_flow(rng, B, S)
    vals[:, SV_LAST_SEEN] = 90_000
    return vals, b, 10


def _fin_beside_ack(rng, B, S, fin_first):
    vals, b = blank(rng, B, S)
    slots = rng.permutation(S)[:B // 2]
    vals[slots, SV_STATE] = NAT_STATE_NEW
    first, second = np.arange(0, B, 2), np.arange(1, B, 2)
    hit(vals, b, first, slots, up=False)
    hit(vals, b, second, slots, up=False)
    b["is_tcp"][:] = True
    closing = rng.choice(np.array([ACK | FIN, RST], U32), B // 2)
    b["tcp_flags"][first] = closing if fin_first else ACK
    b["tcp_flags"][second] = ACK if fin_first else closing
    return vals, b, NOW


def case_fin_then_ack_on_one_slot(rng, B, S):
    return _fin_beside_ack(rng, B, S, True)


def case_ack_then_fin_on_one_slot(rng, B, S):
    return _fin_beside_ack(rng, B, S, False)


def case_icmp_and_udp_write_no_state(rng, B, S):
    vals, b, now = case_up_and_down_of_one_flow(rng, B, S)
    b["is_tcp"][:] = False
    b["tcp_flags"][:] = RST  # junk where the frame is not TCP
    return vals, b, now


def case_random_mix(rng, B, S):
    vals, b = blank(rng, B, S)
    lanes = rng.permutation(B)
    n = B // 3
    hit(vals, b, lanes[:n], rng.integers(0, min(S, 64), n), up=True)
    hit(vals, b, lanes[n:2 * n], rng.integers(0, min(S, 64), n), up=False)
    b["keep"] = rng.random(B) < 0.9
    return vals, b, NOW


CASES = [case_one_slot, case_up_and_down_of_one_flow, case_hit_but_not_kept,
         case_no_lane_hits, case_counters_wrap, case_clock_steps_back,
         case_fin_then_ack_on_one_slot, case_ack_then_fin_on_one_slot,
         case_icmp_and_udp_write_no_state, case_random_mix]


@jax.jit
def _update(vals, b, now_s):
    z = jnp.zeros_like(b["length"])
    res = NATResult(translated=z, punted=z, dropped=z, out_pkt=z, stats=z,
                    is_hairpin=z, egress_hit=b["egress_hit"],
                    ingress_hit=b["ingress_hit"], e_slot=b["e_slot"],
                    i_slot=b["i_slot"], i_state=b["i_state"])
    parsed = Parsed(*[z] * len(Parsed._fields))._replace(
        tcp_flags=b["tcp_flags"], is_tcp=b["is_tcp"])
    sessions = TableState(krows=z, stash_rows=z, vals=vals)
    return nat44_update_sessions(sessions, res, parsed, b["length"],
                                 b["keep"], now_s).vals


# S is a whole table plus its stash in the engine (2**21 + 64); neither
# size here is a multiple of 128
@pytest.mark.parametrize("B,S", [(256, 1003), (8192, 20011)])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_matches_numpy_reference(case, B, S):
    rng = np.random.default_rng([B, CASES.index(case)])
    vals, b, now_s = case(rng, B, S)
    want = reference(vals, b, now_s)
    got = np.asarray(_update(jnp.asarray(vals),
                             {k: jnp.asarray(v) for k, v in b.items()},
                             jnp.uint32(now_s)))
    differing = np.argwhere(got != want)
    assert differing.size == 0, (
        f"{len(differing)} words differ; first (slot, word) {differing[0]}: "
        f"got {got[tuple(differing[0])]}, want {want[tuple(differing[0])]}")
    if case is case_no_lane_hits:
        assert (got == vals).all()
