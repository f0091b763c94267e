"""Multi-chip sharding tests on the 8-device virtual CPU mesh.

The reference tests distribution without a cluster via stub backends and
in-process peers (SURVEY.md §4.6); here the analog is
xla_force_host_platform_device_count=8 — real shard_map, real collectives,
no TPU pod needed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from bng_tpu.control import dhcp_codec, packets
from bng_tpu.ops.table import (HostTable, TableGeom, device_lookup,
                               exchange_capacity, lookup, shard_owner)
from bng_tpu.parallel.hashring import (
    hashring_allocate,
    rendezvous_owner,
    rendezvous_ranked,
)
from bng_tpu.parallel.sharded import (AXIS, ShardedCluster, _shard_map,
                                      make_mesh)
from bng_tpu.utils.net import ip_to_u32

N = 4


class TestHashring:
    def test_rendezvous_deterministic_and_balanced(self):
        nodes = [f"node{i}" for i in range(5)]
        owners = [rendezvous_owner(nodes, f"sub-{i}") for i in range(1000)]
        assert owners == [rendezvous_owner(nodes, f"sub-{i}") for i in range(1000)]
        counts = {n: owners.count(n) for n in nodes}
        assert all(c > 100 for c in counts.values()), f"skewed: {counts}"

    def test_rendezvous_failover_minimal_disruption(self):
        """HRW property: removing a node only remaps its own keys."""
        nodes = [f"node{i}" for i in range(5)]
        keys = [f"sub-{i}" for i in range(500)]
        before = {k: rendezvous_owner(nodes, k) for k in keys}
        survivors = nodes[:-1]
        for k in keys:
            after = rendezvous_owner(survivors, k)
            if before[k] != nodes[-1]:
                assert after == before[k]

    def test_ranked_first_is_owner(self):
        nodes = [f"n{i}" for i in range(4)]
        for k in ("a", "b", "c"):
            ranked = rendezvous_ranked(nodes, k)
            assert ranked[0] == rendezvous_owner(nodes, k)
            assert sorted(ranked) == sorted(nodes)

    def test_hashring_allocate_deterministic_probing(self):
        taken = set()
        idx1 = hashring_allocate("sub-A", 256, lambda i: i not in taken)
        assert idx1 is not None
        # same subscriber, same answer (cross-node determinism)
        assert hashring_allocate("sub-A", 256, lambda i: i not in taken) == idx1
        taken.add(idx1)
        idx2 = hashring_allocate("sub-A", 256, lambda i: i not in taken)
        assert idx2 is not None and idx2 != idx1
        full = hashring_allocate("sub-B", 8, lambda i: False)
        assert full is None


class TestShardedLookup:
    def test_matches_local_lookup(self):
        """Sharded all-to-all lookup == N independent local lookups."""
        mesh = make_mesh(N)
        rng = np.random.default_rng(3)
        shards = [HostTable(nbuckets=64, key_words=2, val_words=4) for _ in range(N)]
        keys = rng.integers(0, 2**32, size=(200, 2), dtype=np.uint32)
        keys = np.unique(keys, axis=0)
        for i, k in enumerate(keys):
            words = [k[0:1], k[1:2]]
            o = int(shard_owner(words, N)[0])
            shards[o].insert(k, [i, i + 1, i + 2, i + 3])

        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[s.device_state() for s in shards]
        )
        g = TableGeom(nbuckets=64, stash=64, axis=AXIS, n_shards=N)

        b = 32
        queries = np.concatenate([
            keys[: b - 8],
            rng.integers(0, 2**32, size=(8, 2), dtype=np.uint32),  # misses
        ])  # one batch per shard -> replicate the same queries on all shards
        qs = np.broadcast_to(queries, (N,) + queries.shape).reshape(N * b, 2).copy()

        def local(tabs1, q):
            tabs = jax.tree.map(lambda x: x[0], tabs1)
            r = lookup(tabs, q, g)
            return r.found, r.vals

        f = jax.jit(_shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS)),
        ))
        found, vals = f(jax.tree.map(lambda *xs: jnp.stack(xs), *[s.device_state() for s in shards]),
                        jnp.asarray(qs))
        found = np.asarray(found).reshape(N, b)
        vals = np.asarray(vals).reshape(N, b, 4)
        present = {tuple(k) for k in keys}
        for shard in range(N):
            for i, q in enumerate(queries):
                if tuple(q) in present:
                    assert found[shard, i], f"shard {shard} missed key {q}"
                    ki = np.nonzero((keys == q).all(axis=1))[0][0]
                    assert vals[shard, i].tolist() == [ki, ki + 1, ki + 2, ki + 3]
                else:
                    assert not found[shard, i]


class TestShardedCluster:
    SERVER_MAC = bytes.fromhex("02aabbccdd01")
    SERVER_IP = ip_to_u32("10.0.0.1")
    T0 = 1_753_000_000

    def _discover_frame(self, mac):
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
        p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST, bytes([1, 3, 6, 51, 54])))
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def test_dhcp_answered_from_any_shard(self):
        """A subscriber cached on shard X is answered when its DISCOVER
        lands on any chip — the all-to-all table routing at work."""
        cl = ShardedCluster(N, batch_per_shard=8)
        cl.set_server_config_all(self.SERVER_MAC, self.SERVER_IP)
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, self.SERVER_IP, lease_time=3600)

        macs = [bytes.fromhex(f"02c0ffee00{i:02x}") for i in range(8)]
        owners = []
        for i, mac in enumerate(macs):
            o = cl.add_subscriber(mac, pool_id=1, ip=ip_to_u32(f"10.0.0.{50+i}"),
                                  lease_expiry=self.T0 + 600)
            owners.append(o)
        assert len(set(owners)) > 1, "want subscribers spread over shards"
        cl.sync_tables()

        B = N * cl.b
        pkt = np.zeros((B, 512), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        fa = np.ones((B,), dtype=bool)
        # place each subscriber's DISCOVER on a chip that is NOT its owner
        for i, mac in enumerate(macs):
            chip = (owners[i] + 1) % N
            row = chip * cl.b + (i % cl.b)
            f = self._discover_frame(mac)
            pkt[row, : len(f)] = np.frombuffer(f, dtype=np.uint8)
            length[row] = len(f)

        out = cl.step(pkt, length, fa, self.T0, 0)
        verdict = out["verdict"]
        tx_rows = np.nonzero(verdict == 2)[0]
        assert len(tx_rows) == len(macs), f"expected {len(macs)} device replies, got {len(tx_rows)}"
        # check one reply's payload
        row = int(tx_rows[0])
        raw = bytes(np.asarray(out["out_pkt"])[row, : int(out["out_len"][row])])
        d = dhcp_codec.decode(packets.decode(raw).payload)
        assert d.msg_type == dhcp_codec.OFFER
        # psum'd stats: every chip counted its own hits, reduced globally
        from bng_tpu.ops.dhcp import ST_HIT

        assert out["dhcp_stats"][ST_HIT] == len(macs)

    def test_sharded_dhcp_fast_lane_parity(self):
        """The sharded DHCP-only program (dhcp_step) answers cross-shard
        DISCOVERs byte-for-byte like the fused sharded step, shares the
        same table leaves (an update drained through one program is
        visible to the other), and psums its stats."""
        from bng_tpu.ops.dhcp import ST_HIT

        cl = ShardedCluster(N, batch_per_shard=8)
        cl.set_server_config_all(self.SERVER_MAC, self.SERVER_IP)
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, self.SERVER_IP, lease_time=3600)
        mac = bytes.fromhex("02c0ffee0077")
        owner = cl.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.90"),
                                  lease_expiry=self.T0 + 600)
        cl.sync_tables()

        B = N * cl.b
        pkt = np.zeros((B, 512), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        row = ((owner + 1) % N) * cl.b  # land on a non-owner chip
        f = self._discover_frame(mac)
        pkt[row, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[row] = len(f)

        out = cl.dhcp_step(pkt, length, self.T0)
        assert out["is_reply"][row] and out["dhcp_stats"][ST_HIT] == 1
        fast = bytes(np.asarray(out["out_pkt"])[row, : int(out["out_len"][row])])

        out2 = cl.step(pkt, length, np.ones((B,), dtype=bool), self.T0, 0)
        assert out2["verdict"][row] == 2
        fused = bytes(np.asarray(out2["out_pkt"])[row, : int(out2["out_len"][row])])
        assert fast == fused

        # update drained through the DHCP-only program is visible to the
        # fused step (shared, threaded table leaves)
        mac2 = bytes.fromhex("02c0ffee0078")
        cl.add_subscriber(mac2, pool_id=1, ip=ip_to_u32("10.0.0.91"),
                          lease_expiry=self.T0 + 600)
        f2 = self._discover_frame(mac2)
        pkt2 = np.zeros((B, 512), dtype=np.uint8)
        length2 = np.zeros((B,), dtype=np.uint32)
        pkt2[0, : len(f2)] = np.frombuffer(f2, dtype=np.uint8)
        length2[0] = len(f2)
        out3 = cl.dhcp_step(pkt2, length2, self.T0 + 1)
        assert out3["is_reply"][0]
        out4 = cl.step(pkt2, length2, np.ones((B,), dtype=bool), self.T0 + 2, 0)
        assert out4["verdict"][0] == 2

    def test_unknown_subscriber_misses_globally(self):
        cl = ShardedCluster(N, batch_per_shard=8)
        cl.set_server_config_all(self.SERVER_MAC, self.SERVER_IP)
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, self.SERVER_IP)
        cl.sync_tables()
        B = N * cl.b
        pkt = np.zeros((B, 512), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        f = self._discover_frame(bytes.fromhex("02ffffffff01"))
        pkt[0, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[0] = len(f)
        out = cl.step(pkt, length, np.ones((B,), dtype=bool), self.T0, 0)
        assert (out["verdict"] == 2).sum() == 0
        from bng_tpu.ops.dhcp import ST_MISS

        assert out["dhcp_stats"][ST_MISS] == 1

    def test_subscriber_added_after_first_step_reaches_device(self):
        """Control-plane writes after the first step flow through the
        per-step update drain (regression: they used to stay host-only)."""
        cl = ShardedCluster(N, batch_per_shard=8)
        cl.set_server_config_all(self.SERVER_MAC, self.SERVER_IP)
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, self.SERVER_IP)
        B = N * cl.b
        pkt = np.zeros((B, 512), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        fa = np.ones((B,), dtype=bool)
        mac = bytes.fromhex("02c0ffee9999")
        f = self._discover_frame(mac)
        pkt[0, : len(f)] = np.frombuffer(f, dtype=np.uint8)
        length[0] = len(f)

        # step 1: unknown -> slow path
        out = cl.step(pkt, length, fa, self.T0, 0)
        assert (out["verdict"] == 2).sum() == 0

        # slow path installs the lease AFTER the cluster is live
        cl.add_subscriber(mac, pool_id=1, ip=ip_to_u32("10.0.0.99"),
                          lease_expiry=self.T0 + 600)

        # step 2: answered on-device
        out = cl.step(pkt, length, fa, self.T0 + 1, 0)
        tx_rows = np.nonzero(out["verdict"] == 2)[0]
        assert len(tx_rows) == 1
        row = int(tx_rows[0])
        raw = bytes(np.asarray(out["out_pkt"])[row, : int(out["out_len"][row])])
        d = dhcp_codec.decode(packets.decode(raw).payload)
        assert d.msg_type == dhcp_codec.OFFER
        assert d.yiaddr == ip_to_u32("10.0.0.99")


class TestShardedExchangeCapacity:
    """Round-1 ask #7: the exchange reserves O(b/N * factor) per
    destination, not the O(b) worst case; overflow lanes punt."""

    def test_balanced_batch_never_punts(self):
        mesh = make_mesh(N)
        rng = np.random.default_rng(11)
        shards = [HostTable(nbuckets=64, key_words=2, val_words=4)
                  for _ in range(N)]
        keys = rng.integers(0, 2**32, size=(400, 2), dtype=np.uint32)
        keys = np.unique(keys, axis=0)[:256]
        for i, k in enumerate(keys):
            o = int(shard_owner([k[0:1], k[1:2]], N)[0])
            shards[o].insert(k, [i, 0, 0, 0])
        g = TableGeom(nbuckets=64, stash=64, axis=AXIS, n_shards=N,
                      capacity_factor=2.0)
        b = 32
        qs = np.broadcast_to(keys[:b], (N, b, 2)).reshape(N * b, 2).copy()

        def local(tabs1, q):
            tabs = jax.tree.map(lambda x: x[0], tabs1)
            r = lookup(tabs, q, g)
            return r.found, r.punted

        f = jax.jit(_shard_map(
            local, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS))))
        found, punted = f(
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[s.device_state() for s in shards]),
            jnp.asarray(qs))
        # a hash-balanced batch fits within factor-2 capacity: no punts
        assert not np.asarray(punted).any()
        assert np.asarray(found).all()

    def test_pathological_skew_punts_not_corrupts(self):
        """Every lane targeting ONE shard: capacity C lanes resolve, the
        rest punt (found=False, punted=True) — never wrong values."""
        mesh = make_mesh(N)
        shards = [HostTable(nbuckets=64, key_words=2, val_words=4)
                  for _ in range(N)]
        # craft keys that all hash to the same owner shard
        rng = np.random.default_rng(12)
        same_owner = []
        want = None
        while len(same_owner) < 32:
            k = rng.integers(0, 2**32, size=(2,), dtype=np.uint32)
            o = int(shard_owner([k[0:1], k[1:2]], N)[0])
            if want is None:
                want = o
            if o == want:
                same_owner.append(k)
        keys = np.stack(same_owner)
        for i, k in enumerate(keys):
            shards[want].insert(k, [i, 0, 0, 0])
        g = TableGeom(nbuckets=64, stash=64, axis=AXIS, n_shards=N,
                      capacity_factor=2.0)
        b = 32
        C = exchange_capacity(b, g)
        assert C < b  # the punt path must actually be exercised
        qs = np.broadcast_to(keys, (N, b, 2)).reshape(N * b, 2).copy()

        def local(tabs1, q):
            tabs = jax.tree.map(lambda x: x[0], tabs1)
            r = lookup(tabs, q, g)
            return r.found, r.punted, r.vals[:, 0]

        f = jax.jit(_shard_map(
            local, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS), P(AXIS))))
        found, punted, v0 = f(
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[s.device_state() for s in shards]),
            jnp.asarray(qs))
        found = np.asarray(found).reshape(N, b)
        punted = np.asarray(punted).reshape(N, b)
        v0 = np.asarray(v0).reshape(N, b)
        for shard in range(N):
            # first C lanes (arrival order) resolve correctly...
            assert found[shard, :C].all()
            assert not punted[shard, :C].any()
            assert v0[shard, :C].tolist() == list(range(C))
            # ...the overflow punts cleanly
            assert punted[shard, C:].all()
            assert not found[shard, C:].any()

    def test_factor_n_reproduces_worst_case_exchange(self):
        """capacity_factor >= N -> C = b: the exact never-punt exchange."""
        g = TableGeom(nbuckets=64, stash=64, axis=AXIS, n_shards=N,
                      capacity_factor=float(N))
        b = 32
        C = exchange_capacity(b, g)
        assert C == b


class TestSkewDegradesToSlowPath:
    """The punt-safety invariant end-to-end: DISCOVERs beyond one shard's
    exchange capacity become slow-path lanes (the authoritative DHCP
    server's job), never drops or wrong replies."""

    SERVER_MAC = bytes.fromhex("02aabbccdd01")
    SERVER_IP = ip_to_u32("10.0.0.1")
    T0 = 1_753_000_000

    # compile-heavy (~27s unique trace); punt-safety also proven by
    # TestRingShardSteering's wrong-shard punt — slow tier runs this one
    @pytest.mark.slow
    def test_overflowed_discovers_go_slow_not_dropped(self):
        cl = ShardedCluster(N, batch_per_shard=32)
        cl.set_server_config_all(self.SERVER_MAC, self.SERVER_IP)
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, self.SERVER_IP,
                        lease_time=3600)

        # 24 subscribers whose MAC keys ALL hash to one owner shard
        same, owner = [], None
        i = 0
        while len(same) < 24:
            mac = bytes.fromhex(f"02c0ffee{i:04x}")
            o = cl.dhcp_sub_shard(mac)
            if owner is None:
                owner = o
            if o == owner:
                same.append(mac)
            i += 1
        for j, mac in enumerate(same):
            cl.add_subscriber(mac, pool_id=1, ip=ip_to_u32(f"10.0.1.{j + 1}"),
                              lease_expiry=self.T0 + 600)
        cl.sync_tables()

        # land every DISCOVER on a chip that is NOT the owner: all 24 MAC
        # lookups route to `owner`, whose capacity is C < 24
        g = cl.geom.dhcp.sub._replace(axis=AXIS, n_shards=N)
        C = exchange_capacity(cl.b, g)
        assert C < len(same), (C, len(same))

        chip = (owner + 1) % N
        B = N * cl.b
        pkt = np.zeros((B, 512), dtype=np.uint8)
        length = np.zeros((B,), dtype=np.uint32)
        for j, mac in enumerate(same):
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
            p.options.append((dhcp_codec.OPT_PARAM_REQ_LIST,
                              bytes([1, 3, 6, 51, 54])))
            f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                   p.encode().ljust(320, b"\x00"))
            row = chip * cl.b + j
            pkt[row, : len(f)] = np.frombuffer(f, dtype=np.uint8)
            length[row] = len(f)

        out = cl.step(pkt, length, np.ones((B,), dtype=bool), self.T0, 0)
        lanes = slice(chip * cl.b, chip * cl.b + len(same))
        v = out["verdict"][lanes]
        n_tx = int((v == 2).sum())
        n_slow = int((v == 0).sum())
        assert n_tx == C, (n_tx, C)  # capacity lanes answered on device
        assert n_slow == len(same) - C  # overflow degrades to slow path
        assert int((v == 1).sum()) == 0  # and NOTHING is dropped


class TestRingShardSteering:
    """Cluster-level owner-routing invariant (VERDICT r3 item 3): the host
    ring steers a subscriber's traffic to the affinity shard, where its
    chip-local NAT/QoS state is consulted — and a frame arriving on a
    WRONG shard punts to the slow path instead of being silently
    translated/shaped (the all-state-is-owner-local safety property)."""

    T0 = 1_753_000_000

    def test_owner_shard_serves_wrong_shard_punts(self):
        n = 2
        cl = ShardedCluster(n, batch_per_shard=8)
        cl.set_server_config_all(bytes.fromhex("02aabbccdd01"),
                                 ip_to_u32("10.0.0.1"))
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.1"),
                        lease_time=3600)
        sub_ip = ip_to_u32("10.0.0.77")
        owner, alloc = cl.allocate_nat(sub_ip, self.T0)
        assert alloc is not None
        o2, flow = cl.handle_new_flow(sub_ip, ip_to_u32("1.2.3.4"),
                                      40000, 443, 17, 600, self.T0)
        assert o2 == owner and flow is not None
        pub_ip, pub_port = flow
        qo = cl.set_qos(sub_ip, down_bps=1_000_000, up_bps=1_000_000)
        assert qo == owner
        assert cl.pub_ip_map()[pub_ip] == owner
        cl.sync_tables()

        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        assert ring.n_shards == n
        up = packets.udp_packet(b"\x02" * 6, b"\x04" * 6, sub_ip,
                                ip_to_u32("1.2.3.4"), 40000, 443, b"u" * 100)
        down = packets.udp_packet(b"\x04" * 6, b"\x02" * 6,
                                  ip_to_u32("1.2.3.4"), pub_ip,
                                  443, pub_port, b"d" * 64)
        assert ring.shard_of(up, 1) == owner  # FLAG_FROM_ACCESS=1
        assert ring.rx_push(up, from_access=True)
        assert ring.rx_push(down, from_access=False)

        B, L = n * cl.b, 512
        pkt = np.zeros((B, L), dtype=np.uint8)
        ln = np.zeros((B,), dtype=np.uint32)
        fl = np.zeros((B,), dtype=np.uint32)
        assert ring.assemble_sharded(pkt, ln, fl) == 2
        base = owner * cl.b
        assert ln[base] == len(up) and ln[base + 1] == len(down)
        out = cl.step(pkt, ln, (fl & 1) != 0, self.T0 + 1, 1_000_000)
        assert int(out["verdict"][base]) == 3      # SNAT'd on the owner
        assert int(out["verdict"][base + 1]) == 3  # DNAT'd on the owner
        ring.complete(out["verdict"].astype(np.uint8),
                      np.asarray(out["out_pkt"]),
                      out["out_len"].astype(np.uint32), B)
        assert ring.stats()["fwd"] == 2

        # same upstream frame force-fed to the wrong shard: must PASS
        wrong = (owner + 1) % n
        wpkt = np.zeros((B, L), dtype=np.uint8)
        wln = np.zeros((B,), dtype=np.uint32)
        wrow = wrong * cl.b
        wpkt[wrow, : len(up)] = np.frombuffer(up, dtype=np.uint8)
        wln[wrow] = len(up)
        out2 = cl.step(wpkt, wln, np.ones((B,), dtype=bool),
                       self.T0 + 2, 2_000_000)
        assert int(out2["verdict"][wrow]) == 0  # punt, never mistranslate

    def test_affinity_matches_ring_for_ip_sweep(self):
        """Control-plane affinity and ring steering agree for any IP."""
        cl = ShardedCluster(N, batch_per_shard=8)
        ring = cl.make_ring(nframes=64, frame_size=2048, depth=32,
                            prefer_native=False)  # PyRing: same spec
        for i in range(64):
            ip = ip_to_u32(f"10.{i % 4}.{i // 4}.{i + 1}")
            up = packets.udp_packet(b"\x02" * 6, b"\x04" * 6, ip,
                                    ip_to_u32("8.8.8.8"), 1000 + i, 443,
                                    b"x" * 32)
            assert cl.affinity_shard_ip(ip) == ring.shard_of(up, 1)


class TestMillionSubscriberShardedBuild:
    """Reference capacity on the sharded path (VERDICT r3 item 4): the
    reference sizes subscriber maps for 1,000,000 entries
    (/root/reference/bpf/maps.h:10). Build 1M hash-sharded over the
    8-way mesh with the vectorized owner split, run a real sharded step,
    and assert device hits — capacity is proven end-to-end, not claimed."""

    T0 = 1_753_000_000

    # compile-heavy scale smoke (~29s: 1M-row build + unique 8-way
    # trace); sharded step hits stay proven by TestShardedCluster —
    # slow tier runs the full 1M build
    @pytest.mark.slow
    def test_1m_subscribers_sharded_step_hits(self):
        n_subs = 1_000_000
        n = 8  # the full 8-way CPU mesh: ~125k subscribers per shard
        cl = ShardedCluster(n, batch_per_shard=64, sub_nbuckets=1 << 16,
                            vlan_nbuckets=64, cid_nbuckets=64, max_pools=32)
        cl.set_server_config_all(bytes.fromhex("02aabbccdd01"),
                                 ip_to_u32("10.0.0.1"))
        for pid in range(16):  # /16 pools to hold 1M addresses
            cl.add_pool_all(pid + 1, ip_to_u32(f"10.{pid}.0.0") & 0xFFFF0000,
                            16, ip_to_u32("10.0.0.1"), lease_time=86400)
        macs = np.arange(n_subs, dtype=np.uint64) + 0x02AA00000000
        idx = np.arange(n_subs, dtype=np.uint64)
        owners = cl.add_subscribers_bulk(
            macs, pool_ids=(idx >> np.uint64(16)).astype(np.uint32) + 1,
            ips=((10 << 24) + 2 + idx).astype(np.uint32),
            lease_expiries=np.uint32(self.T0 + 86400))
        # every shard carries a real share of the 1M build
        per_shard = np.bincount(owners, minlength=n)
        assert per_shard.sum() == n_subs
        assert per_shard.min() > n_subs // n // 2, per_shard.tolist()
        cl.sync_tables()

        B = n * cl.b
        rng = np.random.default_rng(0x1A)
        pick = rng.integers(0, n_subs, size=B)
        pkt = np.zeros((B, 512), dtype=np.uint8)
        ln = np.zeros((B,), dtype=np.uint32)
        for row, i in enumerate(pick):
            mac = int(macs[i]).to_bytes(8, "big")[2:]
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER,
                                         xid=0x7000 + row)
            f = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                   p.encode().ljust(320, b"\x00"))
            pkt[row, : len(f)] = np.frombuffer(f, dtype=np.uint8)
            ln[row] = len(f)
        out = cl.step(pkt, ln, np.ones((B,), dtype=bool), self.T0 + 1, 0)
        n_tx = int((out["verdict"] == 2).sum())
        assert n_tx == B, f"{n_tx}/{B} DISCOVERs answered at 1M scale"
        from bng_tpu.ops.dhcp import ST_HIT

        assert int(out["dhcp_stats"][ST_HIT]) == B

    def test_shared_public_ip_across_shards_rejected(self):
        """Downstream steering is by-IP: shared public-IP ownership is not
        expressible, so the cluster must fail at CONSTRUCTION (review r4),
        never silently steer 3/4 of return traffic to a wrong shard."""
        with pytest.raises(ValueError, match="exclusively"):
            ShardedCluster(2, batch_per_shard=8,
                           public_ips=[ip_to_u32("203.0.113.9")])


class TestClusterRingLoop:
    """process_ring: the multichip production beat — steering ring ->
    sharded step -> verdict demux, end to end."""

    T0 = 1_753_000_000

    # compile-heavy (~34s unique trace); ring -> step -> verdict demux
    # stays proven in tier-1 by TestRingShardSteering and
    # test_sharded_serving's steered-ring loop — slow tier runs this one
    @pytest.mark.slow
    def test_ring_to_step_to_verdicts(self):
        n = 2
        cl = ShardedCluster(n, batch_per_shard=8)
        cl.set_server_config_all(bytes.fromhex("02aabbccdd01"),
                                 ip_to_u32("10.0.0.1"))
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.1"),
                        lease_time=3600)
        mac = bytes.fromhex("02c0ffee0077")
        sub_ip = ip_to_u32("10.0.0.66")
        cl.add_subscriber(mac, pool_id=1, ip=sub_ip,
                          lease_expiry=self.T0 + 600)
        owner, _ = cl.allocate_nat(sub_ip, self.T0)
        cl.handle_new_flow(sub_ip, ip_to_u32("1.2.3.4"), 40000, 443, 17,
                           600, self.T0)
        cl.sync_tables()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)

        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=0x77)
        disc = packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))
        up = packets.udp_packet(mac, b"\x04" * 6, sub_ip,
                                ip_to_u32("1.2.3.4"), 40000, 443, b"u" * 64)
        junk = packets.udp_packet(mac, b"\x04" * 6, ip_to_u32("10.0.0.99"),
                                  ip_to_u32("9.9.9.9"), 1, 2, b"j")
        for f in (disc, up, junk):
            assert ring.rx_push(f, from_access=True)
        got = cl.process_ring(ring, self.T0 + 1, 1_000_000)
        assert got == 3
        # demux: cached DISCOVER -> device OFFER on TX; SNAT'd flow ->
        # FWD; unknown-subscriber junk -> slow (PASS)
        assert ring.tx_pending() == 1
        assert ring.fwd_pending() == 1
        # the junk PASS lane was drained inline (no slow handler: frame
        # recycled — Engine._apply_ring_verdicts semantics)
        assert ring.slow_pending() == 0
        offer, _fl = ring.tx_pop()
        reply = dhcp_codec.decode(bytes(offer)[42:])
        assert reply.op == 2 and reply.xid == 0x77
        ring.fwd_pop()  # drain the SNAT'd frame
        # stats deltas folded (Engine.stats role)
        assert int(cl.stats["dhcp"].sum()) > 0
        assert int(cl.stats["nat"].sum()) > 0
        # empty ring: a beat is a no-op, no window leaks
        assert cl.process_ring(ring, self.T0 + 2, 2_000_000) == 0
        assert ring.free_frames() > 0

        # all-control batch rides the sharded DHCP fast lane; slow lanes
        # reach the host handler and its reply is injected on TX
        handled = []

        def slow(frame):
            handled.append(frame)
            return None

        p2 = dhcp_codec.build_request(bytes.fromhex("02c0ffee0088"),
                                      dhcp_codec.DISCOVER, xid=0x88)
        unknown = packets.udp_packet(bytes.fromhex("02c0ffee0088"),
                                     b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                     p2.encode().ljust(320, b"\x00"))
        assert ring.rx_push(disc, from_access=True)     # cached: device TX
        assert ring.rx_push(unknown, from_access=True)  # miss: slow handler
        assert cl.process_ring(ring, self.T0 + 3, 3_000_000,
                               slow_path=slow) == 2
        assert ring.tx_pending() == 1  # the cached OFFER
        assert len(handled) == 1 and handled[0] == unknown

        # a NAT new-flow punt creates the session on the OWNER shard:
        # the SAME flow forwards on the next beat
        flow2 = packets.udp_packet(mac, b"\x04" * 6, sub_ip,
                                   ip_to_u32("5.6.7.8"), 41000, 443,
                                   b"n" * 64)
        assert ring.rx_push(flow2, from_access=True)
        cl.process_ring(ring, self.T0 + 4, 4_000_000)  # punt handled inline
        assert ring.rx_push(flow2, from_access=True)
        cl.process_ring(ring, self.T0 + 5, 5_000_000)
        # packet 2 SNATs on device, behind packet 1 on its second pass
        # (since PR 53 the frame that punted leaves too)
        assert ring.fwd_pending() == 2


class TestClusterRingPipelined:
    """Double-buffered multichip ring loop (VERDICT r4 weak #4): the
    sharded production beat overlaps host demux with mesh execution the
    same way Engine.process_ring_pipelined does for one chip."""

    T0 = 1_753_000_000

    def _cluster(self):
        cl = ShardedCluster(2, batch_per_shard=8)
        cl.set_server_config_all(bytes.fromhex("02aabbccdd01"),
                                 ip_to_u32("10.0.0.1"))
        cl.add_pool_all(1, ip_to_u32("10.0.0.0"), 24, ip_to_u32("10.0.0.1"),
                        lease_time=3600)
        mac = bytes.fromhex("02c0ffee0099")
        sub_ip = ip_to_u32("10.0.0.77")
        cl.add_subscriber(mac, pool_id=1, ip=sub_ip,
                          lease_expiry=self.T0 + 600)
        cl.sync_tables()
        return cl, mac, sub_ip

    def _discover(self, mac, xid):
        p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER, xid=xid)
        return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68, 67,
                                  p.encode().ljust(320, b"\x00"))

    def test_two_window_overlap_and_flush(self):
        cl, mac, sub_ip = self._cluster()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)

        # call 1: dispatches batch A, retires nothing (pipe filling) —
        # the overlap evidence: A's verdicts are NOT on the ring yet
        assert ring.rx_push(self._discover(mac, 1), from_access=True)
        assert cl.process_ring_pipelined(ring, self.T0 + 1, 1_000_000) == 0
        assert ring.tx_pop() is None
        assert cl._inflight is not None

        # call 2: dispatches batch B, then retires A (device OFFER on TX)
        assert ring.rx_push(self._discover(mac, 2), from_access=True)
        assert cl.process_ring_pipelined(ring, self.T0 + 2, 2_000_000) == 1
        got = ring.tx_pop()
        assert got is not None
        reply = dhcp_codec.decode(bytes(got[0])[42:])
        assert reply.op == 2 and reply.xid == 1

        # flush retires the tail window; idempotent after
        assert cl.flush_pipeline() == 1
        got2 = ring.tx_pop()
        assert got2 is not None and dhcp_codec.decode(
            bytes(got2[0])[42:]).xid == 2
        assert cl.flush_pipeline() == 0
        # empty beats are no-ops and leak no window
        assert cl.process_ring_pipelined(ring, self.T0 + 3, 3_000_000) == 0
        assert cl._inflight is None
        # sync path still works after pipelined use (window accounting)
        assert ring.rx_push(self._discover(mac, 3), from_access=True)
        assert cl.process_ring(ring, self.T0 + 4, 4_000_000) == 1
        assert ring.tx_pending() == 1

    def test_pipelined_dispatch_failure_fails_closed(self):
        cl, mac, sub_ip = self._cluster()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)
        assert ring.rx_push(self._discover(mac, 1), from_access=True)
        assert cl.process_ring_pipelined(ring, self.T0 + 1, 1_000_000) == 0

        real_step, real_dhcp = cl._step, cl._dhcp_step

        def boom(*a, **k):
            raise RuntimeError("synthetic device error")

        cl._step = boom
        cl._dhcp_step = boom
        assert ring.rx_push(self._discover(mac, 2), from_access=True)
        with pytest.raises(RuntimeError, match="synthetic"):
            cl.process_ring_pipelined(ring, self.T0 + 2, 2_000_000)
        cl._step, cl._dhcp_step = real_step, real_dhcp

        # batch A's OFFER still arrived (FIFO retire before fail-close);
        # batch B dropped fail-closed; no window leaked
        got = ring.tx_pop()
        assert got is not None
        assert dhcp_codec.decode(bytes(got[0])[42:]).xid == 1
        assert cl._inflight is None
        assert ring.rx_push(self._discover(mac, 3), from_access=True)
        assert cl.process_ring(ring, self.T0 + 3, 3_000_000) == 1


class TestClusterPPPoE:
    """PPPoE on the multichip path (round 5): session DATA steers by the
    INNER src IP (bngring.h spec addition) to the shard holding the
    session row, where it decaps + SNATs in the sharded fused step;
    downstream DNATs + re-encaps on the public-IP owner shard."""

    T0 = 1_753_000_000
    AC = bytes.fromhex("02aabbccdd01")

    def _data_frame(self, mac, sid, src_ip, dst_ip, sport):
        from bng_tpu.control.pppoe import codec
        from bng_tpu.ops import pppoe as P

        inner = packets.udp_packet(mac, self.AC, src_ip, dst_ip,
                                   sport, 443, b"d" * 48)[14:]
        return codec.eth_frame(
            self.AC, mac, codec.ETH_PPPOE_SESSION,
            codec.PPPoEPacket(code=0, session_id=sid,
                              payload=codec.ppp_frame(P.PPP_IPV4,
                                                      inner)).encode())

    @pytest.mark.slow  # the pppoe_enabled sharded fused step is its
    # own ~20s compile used by this test alone; decap/SNAT device
    # semantics stay in tier-1 via test_pppoe_ops and the PPPoE
    # steering law via test_native_and_python_steering_agree_on_pppoe
    def test_steering_and_device_data_path(self):
        from bng_tpu.control.pppoe import codec

        n = 2
        cl = ShardedCluster(n, batch_per_shard=8, pppoe_enabled=True,
                            server_mac=self.AC, garden_enabled=False)
        cl.set_server_config_all(self.AC, ip_to_u32("10.0.0.1"))

        class Sess:
            session_id = 0x31
            client_mac = bytes.fromhex("02c0ffee0aa1")
            assigned_ip = ip_to_u32("10.0.0.111")

        owner = cl.pppoe_session_up(Sess())
        assert owner == cl.affinity_shard_ip(Sess.assigned_ip)
        nat_owner, _ = cl.allocate_nat(Sess.assigned_ip, self.T0)
        assert nat_owner == owner  # one affinity key places everything
        cl.handle_new_flow(Sess.assigned_ip, ip_to_u32("9.9.9.9"),
                           41000, 443, 17, 600, self.T0)
        cl.sync_tables()
        ring = cl.make_ring(nframes=256, frame_size=2048, depth=64)

        up = self._data_frame(Sess.client_mac, 0x31, Sess.assigned_ip,
                              ip_to_u32("9.9.9.9"), 41000)
        # the ring steers the PPPoE DATA frame by the INNER src ip
        assert ring.shard_of(up, 0x1) == owner
        # ...and PPPoE CONTROL by the sticky MAC hash (any shard ok)
        padi = codec.eth_frame(b"\xff" * 6, Sess.client_mac,
                               codec.ETH_PPPOE_DISCOVERY,
                               codec.PPPoEPacket(code=codec.CODE_PADI,
                                                 session_id=0,
                                                 payload=b"").encode())
        from bng_tpu.runtime.ring import shard_of as py_shard
        from bng_tpu.utils.net import fnv1a32
        assert ring.shard_of(padi, 0x1) == fnv1a32(Sess.client_mac) % n

        assert ring.rx_push(up, from_access=True)
        got = cl.process_ring(ring, self.T0 + 1, 1_000_000)
        assert got == 1
        assert ring.fwd_pending() == 1
        fwd, _fl = ring.fwd_pop()
        d = packets.decode(bytes(fwd))
        assert d.ethertype == 0x0800  # decapped on device
        nat_pub = cl.nat[owner].public_ips[0]
        assert d.src_ip == nat_pub  # SNAT'd on the OWNER shard
        assert int(cl.stats["pppoe"][0]) == 1  # PST_DECAP, psum-reduced

        # ---- downstream: to the public mapping, core side ----
        down = packets.udp_packet(bytes.fromhex("02deadbeef99"), self.AC,
                                  ip_to_u32("9.9.9.9"), nat_pub,
                                  443, d.src_port, b"r" * 24)
        assert ring.shard_of(down, 0x0) == owner  # public-IP ownership
        assert ring.rx_push(down, from_access=False)
        cl.process_ring(ring, self.T0 + 2, 2_000_000)
        assert ring.fwd_pending() == 1
        enc, _ = ring.fwd_pop()
        enc = bytes(enc)
        assert enc[0:6] == Sess.client_mac and enc[6:12] == self.AC
        assert int.from_bytes(enc[12:14], "big") == codec.ETH_PPPOE_SESSION
        pkt6 = codec.PPPoEPacket.decode(enc[14:])
        assert pkt6.session_id == 0x31

    def test_native_and_python_steering_agree_on_pppoe(self):
        """The C++ classifier and the PyRing mirror must stay bit-for-bit
        on the new PPPoE rule (spec: bngring.h)."""
        from bng_tpu.runtime.ring import NativeRing, load_native, shard_of

        if load_native() is None:
            pytest.skip("native lib unavailable")
        ring = NativeRing(nframes=64, frame_size=2048, depth=16, n_shards=4)
        try:
            rng = np.random.default_rng(5)
            for i in range(64):
                mac = bytes([0x02]) + bytes(rng.integers(0, 256, 5).tolist())
                sid = int(rng.integers(1, 0xFFFF))
                src = int(rng.integers(1, 2**32 - 1))
                dst = int(rng.integers(1, 2**32 - 1))
                f = self._data_frame(mac, sid, src, dst, 40000 + i)
                for fl in (0x1, 0x0):  # access and core side
                    assert ring.shard_of(f, fl) == shard_of(f, fl, 4, {})
        finally:
            ring.close()
