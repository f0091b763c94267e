"""Structural HLO regression tests — the op-shape contracts perf relies on.

The round-2/3 QoS bottleneck was invisible to every behavioral test: the
kernel was correct but its probe lowered to sixteen 1-word-wide gathers
(~7ns/element serialized on v5e) instead of two wide row gathers. These
tests pin the STRUCTURE of the lowered programs (StableHLO, backend
independent) so a refactor that quietly reintroduces a narrow-gather
probe or a gather explosion fails CI — PERF_NOTES.md §2 has the numbers.
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp


def _stablehlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text()


def _count(pattern: str, text: str) -> int:
    return len(re.findall(pattern, text))


class TestQoSLookupShape:
    def _lowered(self):
        from bng_tpu.ops.qos import qos_kernel
        from bng_tpu.runtime.engine import QoSTables

        qos = QoSTables(nbuckets=1 << 10)
        for i in range(64):
            qos.set_subscriber((10 << 24) | (i + 2), down_bps=1_000_000,
                               up_bps=1_000_000)
        table = qos.up.device_state()
        B = 1024
        ips = jnp.asarray(((10 << 24) + 2 + np.arange(B) % 64).astype(np.uint32))
        lens = jnp.full((B,), 900, dtype=jnp.uint32)
        active = jnp.ones((B,), dtype=bool)
        return _stablehlo(
            lambda t, i, l: qos_kernel(i, l, active, t, qos.geom,
                                       jnp.uint32(1)),
            table, ips, lens)

    def test_probe_is_stored_row_gathers(self):
        """The probe: both rows[b // 4] gathers carry whole 128-word
        stored rows (slice_sizes = [1,128]), and so does the writeback's
        read of the rows it sets — the narrow [S,1]/[S] probe must not
        come back, and neither must a gather of part of a row."""
        hlo = self._lowered()
        row_gathers = _count(r"slice_sizes = array<i64: 1, 128>", hlo)
        assert row_gathers == 3, f"expected 3 stored-row gathers, got {row_gathers}"

    def test_total_gather_budget(self):
        """Whole-kernel gather budget, ALL wide rows. 5, each for a reason:
        2 stored-row probes [B,128] (bucket 1, bucket 2); 1 sorted-operand
        [B,8] pack row (the lanes into slot order); 1 [B,32] read of the
        running sums at each run's head (the ways a batch rewrites in one
        stored row merge there); 1 stored-row read [B,128] of the rows the
        writeback sets whole. Token state lives inside the probe rows, the
        bucket- and the way-select are selects. The r2 kernel had 16
        narrow probe gathers alone; hold the line."""
        hlo = self._lowered()
        total = _count(r'"stablehlo\.gather"', hlo)  # ops, not attrs
        assert total <= 5, f"gather explosion: {total} gathers in qos_kernel"

    def test_no_narrow_gathers(self):
        """Every gather in the kernel must carry >=8-word rows — 1-word
        slices are the measured ~7ns/element serialized shape."""
        hlo = self._lowered()
        sizes = re.findall(r"slice_sizes = array<i64: ([\d, ]+)>", hlo)
        narrow = [sz for sz in sizes if int(sz.split(",")[-1]) < 8]
        assert not narrow, f"narrow gathers in qos_kernel: {narrow}"

    def test_scatter_budget(self):
        """Currently 6: 1 packed-row unsort, 1 whole stored-row token
        writeback, 4 scalar stats adds."""
        hlo = self._lowered()
        scatters = _count(r'"stablehlo\.scatter"', hlo)
        assert scatters <= 6, f"unexpected scatter count: {scatters}"


class TestQoSTableStaysInOneShape:
    """The QoS table is held on the chip in the shape its probe gathers
    ([nbuckets/4, 128], ops/qtable.py): no op of policy sync + kernel may
    have the whole array as operand or result except the in-place
    scatters and the gathers themselves. This sees StableHLO only, which
    is what the CPU can see: that the compiler keeps one physical form
    (no `copy` between tiled layouts, `{1,0:T(8,128)}` and the like) is
    read on the chip, from the traced step's op list (PERF.md section 5).
    Lowered from ShapeDtypeStructs: nothing is allocated."""

    B, U = 8192, 128

    @pytest.fixture(scope="class", params=[524288, 131072],
                    ids=["one-chip-1M", "shard-of-four"])
    def lowered(self, request):
        from bng_tpu.ops.qos import qos_kernel
        from bng_tpu.ops.qtable import (STORE_W, QTableGeom, QTableState,
                                        QTableUpdate, apply_qupdate,
                                        stored_rows)

        nb = request.param
        geom = QTableGeom(nb)
        u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)
        table = QTableState(rows=u32(stored_rows(nb), STORE_W))
        upd = QTableUpdate(row=jax.ShapeDtypeStruct((self.U,), jnp.int32),
                           ways=u32(self.U), rows=u32(self.U, STORE_W))

        def step(table, upd, ips, lens, active, now_us):
            r = qos_kernel(ips, lens, active, apply_qupdate(table, upd),
                           geom, now_us)
            return r.allowed, r.table, r.stats

        hlo = jax.jit(step, donate_argnums=0).lower(
            table, upd, u32(self.B), u32(self.B),
            jax.ShapeDtypeStruct((self.B,), jnp.bool_), u32()).as_text()
        return hlo, stored_rows(nb) * STORE_W

    def test_no_whole_table_relayout(self, lowered):
        hlo, elems = lowered
        rows, width = elems // 128, 128
        assert f"tensor<{rows}x{width}xui32>" in hlo  # the table is in there
        bad = []
        for line in hlo.splitlines():
            m = re.search(r"stablehlo\.(reshape|transpose|copy)\b", line)
            if not m:
                continue
            for dims in re.findall(r"tensor<((?:\d+x)+)ui32>", line):
                n = 1
                for d in dims.rstrip("x").split("x"):
                    n *= int(d)
                if n == elems:
                    bad.append(line.strip())
        assert not bad, "whole-table relayout in the lowered step:\n" + "\n".join(bad)

    def test_table_is_operand_of_gathers_and_scatters_only(self, lowered):
        """Every line that mentions the table's type is a gather from it,
        a scatter into it, the scatter's region, or the function's own
        signature / return."""
        hlo, elems = lowered
        ty = f"tensor<{elems // 128}x128xui32>"
        other = [l.strip() for l in hlo.splitlines() if ty in l and not re.search(
            r"stablehlo\.(gather|scatter)|func\.func|return|^\s*\}\) :", l)]
        assert not other, "\n".join(other)

    def test_no_while(self, lowered):
        hlo, _ = lowered
        assert _count(r"stablehlo\.while", hlo) == 0

    def test_no_gather_narrower_than_a_way(self, lowered):
        hlo, _ = lowered
        sizes = re.findall(r"slice_sizes = array<i64: ([\d, ]+)>", hlo)
        # 6: the kernel's five (TestQoSLookupShape) and policy sync's read
        # of the stored rows it sets
        assert len(sizes) == 6, sizes
        for sz in sizes:
            dims = [int(d) for d in sz.split(",")]
            assert len(dims) == 2 and dims[0] == 1 and dims[1] >= 8, sz


class TestDHCPFastpathShape:
    def _lowered(self, L):
        from bng_tpu.ops.dhcp import dhcp_fastpath
        from bng_tpu.ops.parse import parse_batch
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        fp = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                            cid_nbuckets=64, max_pools=16)
        fp.set_server_config(bytes.fromhex("02aabbccdd01"), ip_to_u32("10.0.0.1"))
        B = 256
        pkt = jnp.zeros((B, L), dtype=jnp.uint8)
        ln = jnp.full((B,), 300, dtype=jnp.uint32)

        def step(tables, pkt, ln):
            par = parse_batch(pkt, ln)
            res = dhcp_fastpath(pkt, ln, par, tables, fp.geom, jnp.uint32(1))
            return res.is_reply, res.out_pkt, res.out_len

        return _stablehlo(step, fp.device_tables(), pkt, ln)

    def test_table_probes_are_wide_row_gathers(self):
        """All three fast-path table probes (sub K=2, vlan K=1, cid K=8)
        must gather packed bucket rows: 4x [1,32] (sub+vlan, KW=8) and
        2x [1,64] (cid, KW=16). The 18 narrow key/used gathers of the
        unpacked layout must not come back."""
        hlo = self._lowered(512)
        assert _count(r"slice_sizes = array<i64: 1, 32>", hlo) == 4
        assert _count(r"slice_sizes = array<i64: 1, 64>", hlo) == 2
        # per-lane packet-byte reads ([1,1]) are fine; whole-column
        # table-probe gathers ([S,1] operands) are the serialized shape
        narrow_1d = _count(r"slice_sizes = array<i64: 1>(?!,)", hlo)
        assert narrow_1d == 0, f"{narrow_1d} 1-D narrow gathers"

    def test_reply_compose_has_no_byte_gather(self):
        """The reply compose shifts bytes by one of three static amounts
        (VLAN reinsertion 0/4/8, the options tail 0/6/10): selects over
        statically shifted copies. A per-byte `take_along_axis` over the
        slot measured 1.0 GB/s on a v5e (140 ms of a step at [8192, 1536],
        PERF.md section 6, PR 26) and must not come back. Since PR 31 the
        request is read through a static window too: the one byte gather
        left here is parse_batch's single byte."""
        hlo = self._lowered(1536)
        gathers = re.findall(r'"stablehlo\.gather"[^\n]*-> tensor<([0-9x]+)x(\w+)>', hlo)
        assert gathers, "the pattern no longer finds the gathers"
        wide = [(dims, ty) for dims, ty in gathers
                if ty == "ui8" and "x" in dims and int(dims.split("x")[-1]) > 32]
        assert not wide, f"byte gathers wider than 32 columns: {wide}"
        assert len(gathers) <= 17, f"{len(gathers)} gathers in parse + dhcp_fastpath (17 since PR 31)"


class TestNAT44Shape:
    def test_probes_are_wide_row_gathers(self):
        """NAT's three cuckoo tables (sessions K=4, reverse K=4, sub_nat
        K=1 — all KW=8) must probe as packed [1,32] bucket rows; the
        kernel + accounting pass stay within a tight gather/scatter
        budget (narrow whole-table gathers are the serialized shape)."""
        from bng_tpu.control.nat import NATManager
        from bng_tpu.ops.nat44 import nat44_kernel, nat44_update_sessions
        from bng_tpu.ops.parse import parse_batch
        from bng_tpu.utils.net import ip_to_u32

        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        tables = nat.device_tables()
        B, L = 256, 512
        pkt = jnp.zeros((B, L), dtype=jnp.uint8)
        ln = jnp.full((B,), 200, dtype=jnp.uint32)

        def step(tables, pkt, ln):
            par = parse_batch(pkt, ln)
            res = nat44_kernel(pkt, ln, par, tables, nat.geom, jnp.uint32(1))
            sess = nat44_update_sessions(tables.sessions, res, par, ln,
                                         keep=res.translated,
                                         now_s=jnp.uint32(1))
            return res.out_pkt, res.translated, sess

        hlo = _stablehlo(step, tables, pkt, ln)
        row_probes = _count(r"slice_sizes = array<i64: 1, 32>", hlo)
        assert row_probes >= 6, f"packed probes missing: {row_probes}"
        narrow_1d = _count(r"slice_sizes = array<i64: 1>(?!,)", hlo)
        assert narrow_1d == 0, f"{narrow_1d} 1-D narrow gathers"
        total = _count(r'"stablehlo\.gather"', hlo)
        assert total <= 22, f"gather explosion: {total}"
        scatters = _count(r'"stablehlo\.scatter"', hlo)
        assert scatters <= 4, f"scatter explosion: {scatters}"
        # every write into the session rows is a whole-row scatter: a
        # part-row window compiles to a serial loop over the lanes on a
        # TPU, a single column to two relayouts of the table (PERF.md
        # section 6, PR 29; tests/test_tpu_lowering.py holds the compiled
        # step to no `while`)
        S, W = tables.sessions.vals.shape
        into_rows = re.findall(
            rf"\(tensor<{S}x{W}xui32>, tensor<[0-9x]+xi32>, "
            rf"tensor<([0-9x]+)xui32>\) -> tensor<{S}x{W}xui32>", hlo)
        assert into_rows, "the pattern no longer finds the scatters"
        assert all(upd == f"{B}x{W}" for upd in into_rows), into_rows


class TestShardedExchangeShape:
    def test_two_collectives_per_lookup(self):
        """The sharded lookup must stay exactly two all-to-alls (request +
        packed response) — a third collective means someone unpacked the
        response path (3x ICI latency)."""
        from jax.sharding import PartitionSpec as P

        from bng_tpu.ops.table import HostTable, TableGeom, lookup
        from bng_tpu.parallel.sharded import AXIS, _shard_map, make_mesh

        N = 4
        mesh = make_mesh(N)
        t = HostTable(nbuckets=64, key_words=2, val_words=4)
        g = TableGeom(nbuckets=64, stash=64, axis=AXIS, n_shards=N)
        st = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[t.device_state() for _ in range(N)])
        q = jnp.zeros((N * 32, 2), dtype=jnp.uint32)

        def local(tabs1, q):
            tabs = jax.tree.map(lambda x: x[0], tabs1)
            r = lookup(tabs, q, g)
            return r.found, r.vals

        f = _shard_map(local, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                       out_specs=(P(AXIS), P(AXIS)))
        hlo = _stablehlo(f, st, q)
        n_a2a = _count(r"all_to_all", hlo)
        assert n_a2a == 2, f"expected 2 all_to_alls, got {n_a2a}"


class TestFastLaneCompileShapeBudget:
    """VERDICT r3 weak #6: process_dhcp compiles one program per pow2
    batch bucket. Pin the bucket set so a latency sweep over arbitrary
    control-batch sizes can never quietly spend a chip window compiling."""

    def test_bucket_set_is_bounded_and_exact(self):
        from bng_tpu.runtime.engine import Engine

        buckets = {Engine.dhcp_batch_bucket(n) for n in range(0, 20_000, 7)}
        buckets |= {Engine.dhcp_batch_bucket(n) for n in
                    (1, 63, 64, 65, 127, 128, 8191, 8192, 8193, 100_000)}
        assert buckets == {64, 128, 256, 512, 1024, 2048, 4096, 8192}
        # monotone + covering: every n <= cap fits its bucket
        for n in range(1, 8193, 11):
            assert n <= Engine.dhcp_batch_bucket(n)

    @pytest.mark.slow  # compile-heavy; tier-1 runs -m 'not slow'
    def test_engine_reuses_bucket_shapes(self):
        """Distinct frame counts in one bucket must share one compiled
        program (counted via the jit cache of the DHCP-only step)."""
        from bng_tpu.control import dhcp_codec, packets
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        fastpath = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                                  cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(bytes.fromhex("02aabbccdd01"),
                                   ip_to_u32("10.0.0.1"))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        engine = Engine(fastpath, nat, batch_size=8,
                        clock=lambda: 1_753_000_000.0)

        def disc(i):
            mac = bytes([2, 0xAB, 0, 0, 0, i])
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
            return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68,
                                      67, p.encode().ljust(320, b"\x00"))

        sizes = [1, 3, 17, 50, 64]  # all in the 64-bucket
        for s in sizes:
            engine.process_dhcp([disc(i) for i in range(s)])
        cache = engine._dhcp_step._cache_size()
        assert cache == 1, f"expected 1 compiled fast-lane shape, got {cache}"
        engine.process_dhcp([disc(i) for i in range(65)])  # 128-bucket
        assert engine._dhcp_step._cache_size() == 2

    def test_over_cap_batch_splits_not_crashes(self, monkeypatch):
        """len(frames) > DHCP_BATCH_CAP splits into capped chunks with
        lane indices re-based (review r4: the cap must not regress large
        process_dhcp calls into a ValueError)."""
        from bng_tpu.control import dhcp_codec, packets
        from bng_tpu.control.nat import NATManager
        from bng_tpu.runtime.engine import Engine
        from bng_tpu.runtime.tables import FastPathTables
        from bng_tpu.utils.net import ip_to_u32

        fastpath = FastPathTables(sub_nbuckets=256, vlan_nbuckets=64,
                                  cid_nbuckets=64, max_pools=16)
        fastpath.set_server_config(bytes.fromhex("02aabbccdd01"),
                                   ip_to_u32("10.0.0.1"))
        nat = NATManager(public_ips=[ip_to_u32("203.0.113.1")],
                         sessions_nbuckets=256, sub_nat_nbuckets=64)
        engine = Engine(fastpath, nat, batch_size=8,
                        clock=lambda: 1_753_000_000.0)
        monkeypatch.setattr(Engine, "DHCP_BATCH_CAP", 64)

        def disc(i):
            mac = bytes([2, 0xAC, 0, 0, i // 256, i % 256])
            p = dhcp_codec.build_request(mac, dhcp_codec.DISCOVER)
            return packets.udp_packet(mac, b"\xff" * 6, 0, 0xFFFFFFFF, 68,
                                      67, p.encode().ljust(320, b"\x00"))

        frames = [disc(i) for i in range(150)]  # 3 chunks of <=64
        out = engine.process_dhcp(frames)
        lanes = sorted(i for i, _ in out["tx"] + out["slow"])
        assert lanes == list(range(150))  # every lane accounted, re-based


class TestGardenGateShape:
    """The garden gate must stay in the wide-gather regime (PERF_NOTES §2:
    narrow 1-word gathers serialize to ~7ns/element on v5e)."""

    def test_gather_budget_isolated_kernel(self):
        """The gate in isolation (src/dst ip + port/proto as inputs):
        a bounded handful of WIDE gathers — two bucket-row probes + the
        value-row gather + stash — never a per-word gather explosion."""
        import jax
        from bng_tpu.ops.garden import garden_kernel
        from bng_tpu.ops.parse import Parsed
        from bng_tpu.runtime.engine import GardenTables

        g = GardenTables(nbuckets=1 << 10)
        B = 1024

        def step(state, allowed, src_ip, dst_ip, dst_port, proto, ok):
            parsed = Parsed(**{f: (src_ip if f == "src_ip" else
                                   dst_ip if f == "dst_ip" else
                                   dst_port if f == "dst_port" else
                                   proto if f == "proto" else
                                   ok if f == "is_ipv4" else
                                   jnp.zeros((B,), dtype=jnp.uint32))
                               for f in Parsed._fields})
            res = garden_kernel(parsed, ok, state, g.geom, allowed)
            return res.gate_drop, res.stats

        u32 = jnp.zeros((B,), dtype=jnp.uint32)
        txt = jax.jit(step).lower(
            g.subscribers.device_state(), jnp.asarray(g.allowed),
            u32, u32, u32, u32, jnp.ones((B,), dtype=bool)).as_text()
        # exactly the device_lookup structure: 2 wide bucket-row probes +
        # 1 wide value-row gather (stash is a broadcast compare). The
        # [64,1] column reads of the tiny static allowed array are fine;
        # a [capacity,1] column gather over the subscriber table is the
        # serialized shape and must never appear.
        assert _count(r"slice_sizes = array<i64: 1, 32>", txt) == 2
        assert _count(r"slice_sizes = array<i64: 1, 8>", txt) == 1
        assert _count(r"slice_sizes = array<i64: 1>(?!,)", txt) == 0
        cap = (1 << 10) * 4
        assert _count(rf"slice_sizes = array<i64: {cap}, 1>", txt) == 0
