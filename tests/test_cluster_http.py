"""HTTP/SSE cluster transports: HA sync, peer pool, CRDT, Nexus allocator.

Round-2 verdict missing #2's done-criteria: two processes fail over and
keep sessions; a peer pool forwards an allocate to the HRW owner over
HTTP. These tests run real TCP servers (loopback); the final test runs a
genuinely separate python process.
"""

import json
import subprocess
import sys
import time

import pytest

from bng_tpu.control.cluster_http import (
    ClusterServer, HTTPActiveProxy, HTTPPeerProxy, HTTPStorePeer,
    http_nexus_transport,
)
from bng_tpu.control.crdt import CLSetStore, DistributedStore, MODE_WRITE
from bng_tpu.control.ha import (
    ActiveSyncer, InMemorySessionStore, SessionState, StandbySyncer,
)
from bng_tpu.control.nexus import HTTPAllocator
from bng_tpu.control.peerpool import PeerPool, PoolRange


def wait_until(pred, timeout=5.0, step=0.02):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(step)
    return False


@pytest.fixture
def server():
    servers = []

    def make() -> ClusterServer:
        s = ClusterServer().start()
        servers.append(s)
        return s

    yield make
    for s in servers:
        s.close()


class TestHASyncOverHTTP:
    def test_full_sync_deltas_and_failover(self, server):
        active_store = InMemorySessionStore()
        active = ActiveSyncer(active_store)
        srv = server().mount_ha(active)

        active.push_change(SessionState("s1", mac="02:00:00:00:00:01",
                                        ip=0x0A000001))
        active.push_change(SessionState("s2", mac="02:00:00:00:00:02",
                                        ip=0x0A000002))

        standby_store = InMemorySessionStore()
        standby = StandbySyncer(standby_store, transport=lambda: HTTPActiveProxy(
            srv.url, on_stream_end=lambda: standby.disconnect()))
        standby.tick(now=0.0)
        assert standby.connected
        assert len(standby_store) == 2  # full sync over the wire

        # live SSE delta
        active.push_change(SessionState("s3", ip=0x0A000003))
        active.push_change(None, session_id="s1")
        assert wait_until(lambda: len(standby_store) == 2 and
                          standby_store.get("s3") is not None)
        assert standby_store.get("s1") is None

        # --- active dies: stream ends, standby reconnect-backoffs, and
        # the replicated sessions survive for promotion ---
        srv.close()
        assert wait_until(lambda: not standby.connected)
        standby.tick(now=100.0)  # reconnect attempt fails
        assert not standby.connected
        assert standby_store.get("s3").ip == 0x0A000003  # sessions kept

    def test_replay_gap_forces_full_resync(self, server):
        active = ActiveSyncer(InMemorySessionStore(), replay_buffer=4)
        srv = server().mount_ha(active)
        store = InMemorySessionStore()
        standby = StandbySyncer(store, transport=lambda: HTTPActiveProxy(srv.url))
        standby.tick(now=0.0)
        standby.disconnect()
        for i in range(20):  # overflow the replay buffer
            active.push_change(SessionState(f"s{i}", ip=i))
        standby.tick(now=50.0)
        assert standby.connected
        assert len(store) == 20 and standby.stats["full_syncs"] == 2


class TestPeerPoolOverHTTP:
    def test_forward_allocate_to_hrw_owner(self, server):
        """The verdict's literal done-criterion for the peer pool."""
        nodes = ["n1", "n2"]
        pool_def = PoolRange(network=0x0A640000, size=1000)
        proxies = {}

        def transport(node):
            return HTTPPeerProxy(proxies[node])

        p1 = PeerPool("n1", nodes, pool_def, transport=transport)
        p2 = PeerPool("n2", nodes, pool_def, transport=transport)
        s1 = server().mount_pool(p1)
        s2 = server().mount_pool(p2)
        proxies.update(n1=s1.url, n2=s2.url)

        # find a subscriber id each node does NOT own -> real HTTP forward
        sub_owned_by_2 = next(s for s in (f"sub{i}" for i in range(100))
                              if p1.owner_ranked(s)[0] == "n2")
        ip = p1.allocate(sub_owned_by_2)
        assert p1.stats["forwarded"] == 1 and p2.stats["local_allocs"] == 1
        assert p2.by_subscriber[sub_owned_by_2] == ip
        # read side: n1 resolves it via the owner over HTTP
        assert p1.get(sub_owned_by_2) == ip
        # release over HTTP
        assert p1.release(sub_owned_by_2)
        assert sub_owned_by_2 not in p2.by_subscriber

    def test_owner_down_fails_over_to_next_ranked(self, server):
        nodes = ["n1", "n2"]
        pool_def = PoolRange(network=0x0A640000, size=100)
        urls = {}

        def transport(node):
            if node not in urls:
                raise ConnectionError(f"{node} down")
            return HTTPPeerProxy(urls[node])

        p1 = PeerPool("n1", nodes, pool_def, transport=transport)
        sub = next(s for s in (f"sub{i}" for i in range(100))
                   if p1.owner_ranked(s)[0] == "n2")
        ip = p1.allocate(sub)  # n2 unreachable -> local failover allocation
        assert p1.stats["failovers"] >= 1
        assert p1.by_subscriber[sub] == ip


class TestCRDTOverHTTP:
    def test_anti_entropy_over_the_wire(self, server):
        a = DistributedStore("a", mode=MODE_WRITE)
        b = DistributedStore("b", mode=MODE_WRITE)
        srv_b = server().mount_store(b)
        a.add_peer(HTTPStorePeer(srv_b.url))

        a.put("sub/1", b"ip=10.0.0.1")
        b.put("sub/2", b"ip=10.0.0.2")
        b.delete("sub/2")
        b.put("sub/3", b"\x00\x01\xff")  # binary-safe

        a.tick()  # one HTTP anti-entropy round, both directions
        assert a.get("sub/3") == b"\x00\x01\xff"
        assert a.get("sub/2") is None
        assert b.get("sub/1") == b"ip=10.0.0.1"
        assert a.store.digest() == b.store.digest()

    def test_unreachable_peer_skipped(self):
        a = DistributedStore("a", mode=MODE_WRITE)
        a.add_peer(HTTPStorePeer("http://127.0.0.1:1"))  # nothing listens
        a.put("k", b"v")
        assert a.tick() == 0  # no exception, round skipped


class TestNexusAllocatorOverHTTP:
    def test_allocate_lookup_release(self, server):
        class Backend:
            def __init__(self):
                self.ips = {}

            def allocate(self, subscriber_id, pool_hint):
                ip = self.ips.setdefault(subscriber_id,
                                         f"10.9.0.{len(self.ips) + 1}")
                return ip

            def lookup(self, subscriber_id):
                return self.ips.get(subscriber_id)

            def release(self, subscriber_id):
                return self.ips.pop(subscriber_id, None) is not None

            def pool_info(self):
                return {"pools": [{"id": "p1", "used": len(self.ips)}]}

        srv = server().mount_allocator(Backend())
        alloc = HTTPAllocator(srv.url, http_nexus_transport(srv.url))
        ip = alloc.allocate("subA")
        assert ip == "10.9.0.1"
        assert alloc.lookup("subA") == ip
        assert alloc.health_check()
        assert alloc.get_pool_info()["pools"][0]["used"] == 1
        assert alloc.release("subA")
        assert alloc.lookup("subA") is None


class TestTwoProcesses:
    def test_real_second_process_syncs_sessions(self, server, tmp_path):
        """An actually-separate python process full-syncs and receives SSE
        deltas from this process's active syncer."""
        active = ActiveSyncer(InMemorySessionStore())
        srv = server().mount_ha(active)
        active.push_change(SessionState("boot", ip=1))

        code = f"""
import json, sys, time
from bng_tpu.control.cluster_http import HTTPActiveProxy
from bng_tpu.control.ha import InMemorySessionStore, StandbySyncer
store = InMemorySessionStore()
sb = StandbySyncer(store, transport=lambda: HTTPActiveProxy({srv.url!r}))
sb.tick(now=0.0)
t0 = time.time()
while time.time() - t0 < 10:
    if store.get("live") is not None:
        print(json.dumps({{"n": len(store), "live_ip": store.get("live").ip}}))
        sys.exit(0)
    time.sleep(0.05)
sys.exit(2)
"""
        import os

        env = dict(os.environ, JAX_PLATFORMS="cpu")  # the child needs no chip
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE, text=True, env=env)
        time.sleep(1.0)  # child is full-synced and streaming by now
        active.push_change(SessionState("live", ip=0x7F000001))
        out, _ = proc.communicate(timeout=30)
        assert proc.returncode == 0, out
        got = json.loads(out.strip().splitlines()[-1])
        assert got == {"n": 2, "live_ip": 0x7F000001}


class TestStreamRobustness:
    def test_fresh_active_seq0_window_not_lost(self, server):
        """Deltas between a seq-0 full sync and the stream connect must be
        replayed (code-review r3 finding: the since==0 guard dropped them)."""
        active = ActiveSyncer(InMemorySessionStore())
        srv = server().mount_ha(active)
        store = InMemorySessionStore()
        standby = StandbySyncer(store, transport=lambda: HTTPActiveProxy(srv.url))
        # full-sync a FRESH active (seq 0)...
        proxy = HTTPActiveProxy(srv.url)
        sessions, seq = proxy.full_sync()
        assert seq == 0
        # ...a session lands in the sync->subscribe window...
        active.push_change(SessionState("gap", ip=42))
        # ...then the stream opens with since=0 and must replay it
        got = []
        cancel = proxy.subscribe(got.append)
        assert wait_until(lambda: len(got) == 1)
        assert got[0].session.session_id == "gap"
        cancel()

    def test_slow_consumer_never_crashes_active(self, server):
        """4096+ undelivered deltas end the stream, not the active
        (code-review r3 finding: put_nowait raised into push_change)."""
        import urllib.request

        active = ActiveSyncer(InMemorySessionStore())
        srv = server().mount_ha(active)
        # open a stream and never read it
        conn = urllib.request.urlopen(f"{srv.url}/ha/stream?since=0", timeout=10)
        time.sleep(0.2)
        for i in range(5000):  # overflows the 4096 SSE queue
            active.push_change(SessionState(f"s{i}", ip=i))
        # the active survived and kept every session
        assert len(active.store.all()) == 5000
        conn.close()
