/* bngring implementation — see bngring.h for the design contract.
 *
 * SPSC rings follow the classic AF_XDP layout: free-running 32-bit
 * producer/consumer cursors, power-of-two capacity, entries addressed by
 * cursor & mask. Producer publishes with release, consumer observes with
 * acquire; each side caches the opposite cursor to avoid cross-core
 * traffic on every op (the if_xdp.h / io_uring discipline).
 */
#include "bngring.h"

#include <atomic>
#include <cstdlib>
#include <cstring>
#include <new>

namespace {

inline bool is_pow2(uint32_t v) { return v != 0 && (v & (v - 1)) == 0; }

/* One SPSC descriptor ring. Producer-side and consumer-side state live on
 * separate cache lines (the if_xdp.h discipline): without the padding
 * every publish invalidates the opposite core's line. */
struct Ring {
  bng_desc *entries = nullptr;
  uint32_t mask = 0;
  alignas(64) std::atomic<uint32_t> prod{0};
  uint32_t cached_cons = 0; /* producer's view */
  alignas(64) std::atomic<uint32_t> cons{0};
  uint32_t cached_prod = 0; /* consumer's view */

  bool init(uint32_t depth) {
    entries = static_cast<bng_desc *>(calloc(depth, sizeof(bng_desc)));
    mask = depth - 1;
    return entries != nullptr;
  }
  void fini() { free(entries); }

  uint32_t size() const { return mask + 1; }

  bool push(const bng_desc &d) {
    uint32_t p = prod.load(std::memory_order_relaxed);
    if (p - cached_cons == size()) {
      cached_cons = cons.load(std::memory_order_acquire);
      if (p - cached_cons == size()) return false; /* full */
    }
    entries[p & mask] = d;
    prod.store(p + 1, std::memory_order_release);
    return true;
  }

  bool pop(bng_desc *out) {
    uint32_t c = cons.load(std::memory_order_relaxed);
    if (cached_prod == c) {
      cached_prod = prod.load(std::memory_order_acquire);
      if (cached_prod == c) return false; /* empty */
    }
    *out = entries[c & mask];
    cons.store(c + 1, std::memory_order_release);
    return true;
  }

  uint32_t pending() const {
    return prod.load(std::memory_order_acquire) -
           cons.load(std::memory_order_acquire);
  }
};

/* Bounded MPMC ring (Vyukov per-slot-sequence queue) for the FILL pool.
 *
 * Unlike the directional rings, frame alloc/free crosses every thread in
 * the deployment: the wire thread allocates (rx_reserve) and recycles
 * rx-full rejects, the engine thread frees drops in batch_complete and
 * allocates in tx_inject, and the slow-path thread recycles after
 * slow_pop. An SPSC cursor pair corrupts under that pattern (round-1
 * ADVICE finding); per-slot sequence numbers make every push/pop a CAS
 * claim + independent publish, safe from any thread. */
struct MpmcRing {
  /* cells padded to a cache line and the two cursors on separate lines
   * (Vyukov's own layout): three threads hammer this ring at frame rate,
   * and false sharing would serialize the CAS claims */
  struct alignas(64) Cell {
    std::atomic<uint32_t> seq{0};
    bng_desc d{};
  };
  Cell *cells = nullptr;
  uint32_t mask = 0;
  alignas(64) std::atomic<uint32_t> prod{0};
  alignas(64) std::atomic<uint32_t> cons{0};

  bool init(uint32_t depth) {
    cells = new (std::nothrow) Cell[depth];
    if (!cells) return false;
    for (uint32_t i = 0; i < depth; i++)
      cells[i].seq.store(i, std::memory_order_relaxed);
    mask = depth - 1;
    return true;
  }
  void fini() { delete[] cells; }

  bool push(const bng_desc &d) {
    uint32_t pos = prod.load(std::memory_order_relaxed);
    for (;;) {
      Cell &c = cells[pos & mask];
      uint32_t seq = c.seq.load(std::memory_order_acquire);
      int32_t dif = static_cast<int32_t>(seq - pos);
      if (dif == 0) {
        if (prod.compare_exchange_weak(pos, pos + 1,
                                       std::memory_order_relaxed)) {
          c.d = d;
          c.seq.store(pos + 1, std::memory_order_release);
          return true;
        }
      } else if (dif < 0) {
        return false; /* full */
      } else {
        pos = prod.load(std::memory_order_relaxed);
      }
    }
  }

  bool pop(bng_desc *out) {
    uint32_t pos = cons.load(std::memory_order_relaxed);
    for (;;) {
      Cell &c = cells[pos & mask];
      uint32_t seq = c.seq.load(std::memory_order_acquire);
      int32_t dif = static_cast<int32_t>(seq - (pos + 1));
      if (dif == 0) {
        if (cons.compare_exchange_weak(pos, pos + 1,
                                       std::memory_order_relaxed)) {
          *out = c.d;
          c.seq.store(pos + mask + 1, std::memory_order_release);
          return true;
        }
      } else if (dif < 0) {
        return false; /* empty */
      } else {
        pos = cons.load(std::memory_order_relaxed);
      }
    }
  }

  uint32_t pending() const {
    return prod.load(std::memory_order_acquire) -
           cons.load(std::memory_order_acquire);
  }
};

} // namespace

/* Public-IP -> shard steering map: fixed-size open addressing with the
 * bounded-probe discipline the fast-path tables use everywhere
 * (nat44.c:423 bounds probes for the verifier; same style here).
 *
 * THREADING: single writer (control thread, bng_ring_steer_pub_ip),
 * many readers (wire thread inside rx_submit). Publication protocol:
 * the writer stores ip first, then shard_plus1 with release; a reader
 * that observes shard_plus1 != 0 with acquire therefore sees the
 * matching ip. Entries are never deleted; an existing IP's shard may be
 * updated at runtime (the atomic store makes the switch clean). */
struct PubMap {
  static constexpr uint32_t SLOTS = 1024;
  static constexpr uint32_t MAX_PROBE = 64;
  struct Ent {
    std::atomic<uint32_t> ip{0};
    std::atomic<uint32_t> shard_plus1{0}; /* 0 = empty */
  };
  Ent ents[SLOTS];
};

/* Public-IP RANGES -> shard: a deployment's pool is thousands of
 * addresses dealt to the shards in contiguous runs, so ownership is a
 * handful of [lo, hi] tests and the cost a frame does not grow with the
 * pool. Append-only, no two ranges overlap (the writer refuses one that
 * would); same single-writer publication as PubMap: the entry's words
 * first, then `n` with release, and a reader that loads `n` with acquire
 * sees whole entries. Looked up BEFORE the exact map. */
struct PubRanges {
  static constexpr uint32_t MAX = 64;
  struct Ent {
    std::atomic<uint32_t> lo{0}, hi{0}, shard{0};
  };
  Ent ents[MAX];
  std::atomic<uint32_t> n{0};
};

struct bng_ring {
  uint8_t *umem = nullptr;
  uint64_t umem_size = 0;
  uint32_t frame_size = 0;
  uint32_t nframes = 0;
  uint32_t n_shards = 1;

  MpmcRing fill; /* free frames (addr only) — any-thread alloc/free */
  Ring *rxq = nullptr; /* wire -> engine, one SPSC queue per shard */
  Ring tx;   /* engine TX verdicts -> wire (same port) */
  Ring fwd;  /* engine FWD verdicts -> wire (other port) */
  Ring slow; /* engine PASS verdicts -> slow path */
  PubMap pubmap; /* downstream steering: NAT public IP -> owner shard */
  PubRanges pubranges; /* ... and contiguous runs of them */

  /* in-flight batches (assemble..complete windows). TWO slots so a
   * double-buffered engine can assemble+dispatch batch k+1 before
   * completing batch k — the device then always has work enqueued while
   * the host demuxes verdicts (SURVEY §7 dispatch design). complete()
   * retires strictly FIFO. */
  static constexpr uint32_t MAX_INFLIGHT = 2;
  bng_desc *inflight[MAX_INFLIGHT] = {nullptr, nullptr};
  uint32_t inflight_n[MAX_INFLIGHT] = {0, 0};
  uint32_t inflight_head = 0; /* oldest outstanding batch */
  uint32_t inflight_count = 0;
  uint32_t inflight_cap = 0;

  bng_ring_stats stats{};
};

extern "C" {

bng_ring *bng_ring_create_sharded(uint32_t nframes, uint32_t frame_size,
                                  uint32_t depth, uint32_t n_shards) {
  if (!is_pow2(nframes) || !is_pow2(depth) || frame_size < 64) return nullptr;
  if (n_shards < 1 || n_shards > 64) return nullptr;
  auto *r = new (std::nothrow) bng_ring();
  if (!r) return nullptr;
  r->frame_size = frame_size;
  r->nframes = nframes;
  r->n_shards = n_shards;
  r->umem_size = static_cast<uint64_t>(nframes) * frame_size;
  /* PAGE alignment, size rounded to a page multiple: AF_XDP's
   * XDP_UMEM_REG requires a page-aligned area (bngxsk.cpp registers this
   * exact buffer), aligned_alloc requires size % alignment == 0, and a
   * page is trivially cache-line aligned for the staging copies. */
  const uint64_t page = 4096;
  uint64_t alloc_size = (r->umem_size + page - 1) & ~(page - 1);
  r->umem = static_cast<uint8_t *>(aligned_alloc(page, alloc_size));
  r->rxq = new (std::nothrow) Ring[n_shards];
  bool ok = r->umem && r->rxq && r->fill.init(nframes) && r->tx.init(depth) &&
            r->fwd.init(depth) && r->slow.init(depth);
  for (uint32_t s = 0; ok && s < n_shards; s++) ok = r->rxq[s].init(depth);
  /* a sharded batch is n_shards regions of up to depth rows each */
  r->inflight_cap = depth * n_shards;
  for (uint32_t i = 0; i < bng_ring::MAX_INFLIGHT; i++) {
    r->inflight[i] =
        static_cast<bng_desc *>(calloc(r->inflight_cap, sizeof(bng_desc)));
    ok = ok && r->inflight[i];
  }
  if (!ok) {
    bng_ring_destroy(r);
    return nullptr;
  }
  memset(r->umem, 0, r->umem_size);
  /* all frames start free */
  for (uint32_t i = 0; i < nframes; i++) {
    bng_desc d{static_cast<uint64_t>(i) * frame_size, 0, 0};
    r->fill.push(d);
  }
  return r;
}

bng_ring *bng_ring_create(uint32_t nframes, uint32_t frame_size,
                          uint32_t depth) {
  return bng_ring_create_sharded(nframes, frame_size, depth, 1);
}

void bng_ring_destroy(bng_ring *r) {
  if (!r) return;
  r->fill.fini();
  if (r->rxq)
    for (uint32_t s = 0; s < r->n_shards; s++) r->rxq[s].fini();
  delete[] r->rxq;
  r->tx.fini();
  r->fwd.fini();
  r->slow.fini();
  for (uint32_t i = 0; i < bng_ring::MAX_INFLIGHT; i++) free(r->inflight[i]);
  free(r->umem);
  delete r;
}

uint32_t bng_ring_n_shards(bng_ring *r) { return r->n_shards; }

uint8_t *bng_ring_umem(bng_ring *r) { return r->umem; }
uint64_t bng_ring_umem_size(bng_ring *r) { return r->umem_size; }
uint32_t bng_ring_frame_size(bng_ring *r) { return r->frame_size; }

static bool valid_addr(bng_ring *r, uint64_t addr) {
  return addr < r->umem_size && addr % r->frame_size == 0;
}

/* Return a frame to the fill pool, normalized to its chunk base: wire
 * descriptors may carry a copy-mode headroom offset (rx_submit_batch),
 * and the pool hands out whole chunks. */
static void recycle(bng_ring *r, uint64_t addr) {
  bng_desc d{addr - addr % r->frame_size, 0, 0};
  r->fill.push(d);
}

uint64_t bng_ring_rx_reserve(bng_ring *r) {
  bng_desc d;
  if (!r->fill.pop(&d)) {
    r->stats.fill_empty++;
    return UINT64_MAX;
  }
  return d.addr;
}

/* Genuine-DHCP classifier (0-2 VLAN tags), mirroring the fast path's
 * eligibility parse (dhcp_fastpath.c: op==BOOTREQUEST + magic cookie).
 * Deliberately strict — only frames the DHCP-only device program would
 * actually consider are classified, so the fast lane can never swallow
 * natable port-67 transit, fragments, or non-DHCP floods (those keep the
 * fused pipeline's NAT/antispoof/QoS treatment). Runs once per RX frame. */
static uint32_t classify_dhcp(const uint8_t *p, uint32_t len) {
  if (len < 14) return 0;
  uint32_t off = 12;
  uint32_t et = (static_cast<uint32_t>(p[off]) << 8) | p[off + 1];
  for (int i = 0; i < 2 && (et == 0x8100 || et == 0x88a8); i++) {
    off += 4;
    if (len < off + 2) return 0;
    et = (static_cast<uint32_t>(p[off]) << 8) | p[off + 1];
  }
  off += 2; /* L3 start */
  if (et != 0x0800 || len < off + 20) return 0;
  if ((p[off] >> 4) != 4) return 0;
  uint32_t ihl = (p[off] & 0x0F) * 4u;
  if (ihl < 20 || p[off + 9] != 17) return 0; /* UDP */
  /* fragmented packets (MF set or nonzero offset) carry no parseable L4 */
  uint32_t fragword = (static_cast<uint32_t>(p[off + 6]) << 8) | p[off + 7];
  if (fragword & 0x3FFFu) return 0;
  uint32_t l4 = off + ihl;
  if (len < l4 + 8) return 0;
  uint32_t dport = (static_cast<uint32_t>(p[l4 + 2]) << 8) | p[l4 + 3];
  if (dport != 67) return 0;
  /* BOOTP: op==BOOTREQUEST and the DHCP magic cookie at +236 */
  uint32_t bootp = l4 + 8;
  if (len < bootp + 240 || p[bootp] != 1) return 0;
  uint32_t magic = (static_cast<uint32_t>(p[bootp + 236]) << 24) |
                   (static_cast<uint32_t>(p[bootp + 237]) << 16) |
                   (static_cast<uint32_t>(p[bootp + 238]) << 8) |
                   p[bootp + 239];
  return magic == 0x63825363u ? BNG_DESC_F_DHCP_CTRL : 0;
}

/* FNV-1a32 — must match bng_tpu/utils/net.py fnv1a32 bit-for-bit (the
 * control plane computes subscriber affinity with the Python twin). */
static uint32_t fnv1a32_bytes(const uint8_t *p, uint32_t n) {
  uint32_t h = 2166136261u;
  for (uint32_t i = 0; i < n; i++) {
    h ^= p[i];
    h *= 16777619u;
  }
  return h;
}

static int pubmap_find(const PubMap &m, uint32_t ip, bool for_insert) {
  uint8_t key[4] = {static_cast<uint8_t>(ip >> 24),
                    static_cast<uint8_t>(ip >> 16),
                    static_cast<uint8_t>(ip >> 8), static_cast<uint8_t>(ip)};
  uint32_t h = fnv1a32_bytes(key, 4);
  for (uint32_t probe = 0; probe < PubMap::MAX_PROBE; probe++) {
    uint32_t slot = (h + probe) & (PubMap::SLOTS - 1);
    const PubMap::Ent &e = m.ents[slot];
    if (e.shard_plus1.load(std::memory_order_acquire) == 0)
      return for_insert ? static_cast<int>(slot) : -1;
    if (e.ip.load(std::memory_order_relaxed) == ip)
      return static_cast<int>(slot);
  }
  return -1;
}

int bng_ring_steer_pub_ip(bng_ring *r, uint32_t ip, uint32_t shard) {
  if (shard >= r->n_shards) return -1;
  int slot = pubmap_find(r->pubmap, ip, /*for_insert=*/true);
  if (slot < 0) return -1;
  /* ip before shard_plus1-with-release: a concurrent reader that sees the
   * entry occupied sees the right ip (PubMap threading contract above) */
  r->pubmap.ents[slot].ip.store(ip, std::memory_order_relaxed);
  r->pubmap.ents[slot].shard_plus1.store(shard + 1, std::memory_order_release);
  return 0;
}

int bng_ring_steer_pub_range(bng_ring *r, uint32_t lo, uint32_t hi,
                             uint32_t shard) {
  PubRanges &t = r->pubranges;
  uint32_t n = t.n.load(std::memory_order_relaxed);
  if (shard >= r->n_shards || lo > hi || n >= PubRanges::MAX) return -1;
  for (uint32_t i = 0; i < n; i++)
    if (lo <= t.ents[i].hi.load(std::memory_order_relaxed) &&
        t.ents[i].lo.load(std::memory_order_relaxed) <= hi)
      return -1; /* overlap: ownership is exclusive */
  t.ents[n].lo.store(lo, std::memory_order_relaxed);
  t.ents[n].hi.store(hi, std::memory_order_relaxed);
  t.ents[n].shard.store(shard, std::memory_order_relaxed);
  t.n.store(n + 1, std::memory_order_release);
  return 0;
}

/* Owner shard of a NAT public IP: the ranges, then the exact map; -1 when
 * no shard's pool holds it. */
static int pub_owner(const bng_ring *r, uint32_t ip) {
  const PubRanges &t = r->pubranges;
  uint32_t nr = t.n.load(std::memory_order_acquire);
  for (uint32_t i = 0; i < nr; i++)
    if (ip >= t.ents[i].lo.load(std::memory_order_relaxed) &&
        ip <= t.ents[i].hi.load(std::memory_order_relaxed))
      return static_cast<int>(t.ents[i].shard.load(std::memory_order_relaxed));
  int slot = pubmap_find(r->pubmap, ip, /*for_insert=*/false);
  if (slot < 0) return -1;
  uint32_t s =
      r->pubmap.ents[slot].shard_plus1.load(std::memory_order_relaxed) - 1;
  return s < r->n_shards ? static_cast<int>(s) : -1;
}

/* What steer() saw of the public-IP tables, for the always-on counters:
 * a frame from the core steered by ownership, or one whose destination is
 * in no shard's pool (it fell back to the hash). */
enum { STEER_OTHER = 0, STEER_PUB_HIT = 1, STEER_PUB_MISS = 2 };

/* Steering decision — spec in bngring.h; Python twin: ring.py steer.
 * Walks the same L2/L3 prefix as classify_dhcp (0-2 VLAN tags). */
static uint32_t steer(const bng_ring *r, const uint8_t *p, uint32_t len,
                      uint32_t flags, int *pub) {
  uint32_t n = r->n_shards;
  *pub = STEER_OTHER;
  if (n == 1) return 0;
  if (len < 14) return 0;
  if (!(flags & BNG_DESC_F_DHCP_CTRL)) {
    uint32_t off = 12;
    uint32_t et = (static_cast<uint32_t>(p[off]) << 8) | p[off + 1];
    for (int i = 0; i < 2 && (et == 0x8100 || et == 0x88a8); i++) {
      off += 4;
      if (len < off + 2) break;
      et = (static_cast<uint32_t>(p[off]) << 8) | p[off + 1];
    }
    off += 2; /* L3 start */
    if (et == 0x0800 && len >= off + 20 && (p[off] >> 4) == 4) {
      if (flags & BNG_DESC_F_FROM_ACCESS) {
        /* upstream: subscriber = src private IP */
        return fnv1a32_bytes(p + off + 12, 4) % n;
      }
      /* downstream: NAT public IP owner, else dst-IP hash */
      const uint8_t *dst = p + off + 16;
      uint32_t dip = (static_cast<uint32_t>(dst[0]) << 24) |
                     (static_cast<uint32_t>(dst[1]) << 16) |
                     (static_cast<uint32_t>(dst[2]) << 8) | dst[3];
      int owner = pub_owner(r, dip);
      *pub = owner >= 0 ? STEER_PUB_HIT : STEER_PUB_MISS;
      if (owner >= 0) return static_cast<uint32_t>(owner);
      return fnv1a32_bytes(dst, 4) % n;
    }
    /* PPPoE session DATA (PPP proto IPv4): steer by the INNER src IP —
     * the affinity key the decap'd packet's chip-local NAT/QoS/session
     * state is placed with.  PPPoE control falls through to the sticky
     * MAC hash (any shard's slow path handles negotiation). */
    if (et == 0x8864 && (flags & BNG_DESC_F_FROM_ACCESS) &&
        len >= off + 8 + 20 && p[off] == 0x11 && p[off + 1] == 0 &&
        ((static_cast<uint32_t>(p[off + 6]) << 8) | p[off + 7]) == 0x0021 &&
        (p[off + 8] >> 4) == 4) {
      return fnv1a32_bytes(p + off + 8 + 12, 4) % n;
    }
  }
  /* DHCP control (any shard correct; MAC = sticky) and non-IPv4 */
  return fnv1a32_bytes(p + 6, 6) % n;
}

uint32_t bng_ring_shard_of(bng_ring *r, const uint8_t *p, uint32_t len,
                           uint32_t flags) {
  int pub;
  return steer(r, p, len, flags, &pub);
}

/* Steer one classified frame onto its shard's RX queue, counting what
 * the public-IP tables said of a frame the queue took. */
static bool rx_enqueue(bng_ring *r, uint64_t addr, uint32_t len,
                       uint32_t flags) {
  int pub;
  uint32_t shard = steer(r, r->umem + addr, len, flags, &pub);
  bng_desc d{addr, len, flags};
  if (!r->rxq[shard].push(d)) {
    r->stats.rx_full++;
    recycle(r, addr);
    return false;
  }
  if (pub == STEER_PUB_HIT) r->stats.steer_pub_hit++;
  else if (pub == STEER_PUB_MISS) r->stats.steer_pub_miss++;
  return true;
}

int bng_ring_rx_submit(bng_ring *r, uint64_t addr, uint32_t len,
                       uint32_t flags) {
  if (!valid_addr(r, addr) || len > r->frame_size) {
    r->stats.bad_desc++;
    return -1;
  }
  /* direction gate: the fused pipeline only answers access-side DHCP
   * (dhcp_tx = is_reply & from_access) — a network-side frame must never
   * enter the fast lane.  The classifier is authoritative: a caller's
   * pre-set DHCP_CTRL bit is cleared first, so a stale/hostile flags word
   * can never route a network-side frame around NAT/antispoof/QoS. */
  flags &= ~BNG_DESC_F_DHCP_CTRL;
  if (flags & BNG_DESC_F_FROM_ACCESS)
    flags |= classify_dhcp(r->umem + addr, len);
  return rx_enqueue(r, addr, len, flags) ? 0 : -1;
}

uint32_t bng_ring_rx_reserve_batch(bng_ring *r, uint64_t *out_addrs,
                                   uint32_t n) {
  uint32_t got = 0;
  bng_desc d;
  while (got < n && r->fill.pop(&d)) out_addrs[got++] = d.addr;
  if (got < n) r->stats.fill_empty++; /* one per dry pump round (scalar) */
  return got;
}

uint32_t bng_ring_rx_submit_batch(bng_ring *r, const uint64_t *addrs,
                                  const uint32_t *lens, uint32_t flags,
                                  uint8_t *out_ok, uint32_t n) {
  uint32_t ok_n = 0;
  const uint32_t fsz = r->frame_size;
  for (uint32_t i = 0; i < n; i++) {
    uint64_t addr = addrs[i];
    out_ok[i] = 0;
    if (addr >= r->umem_size) { /* garbage addr: nothing to recycle */
      r->stats.bad_desc++;
      continue;
    }
    uint32_t off = static_cast<uint32_t>(addr % fsz);
    if (lens[i] > fsz - off) { /* does not fit the chunk room: drop.
         The scalar pump pre-validates identically (no ring stat), so
         pump_stats stay bit-equal across paths. */
      recycle(r, addr);
      continue;
    }
    uint32_t fl = flags & ~BNG_DESC_F_DHCP_CTRL; /* rx_submit gate */
    if (fl & BNG_DESC_F_FROM_ACCESS)
      fl |= classify_dhcp(r->umem + addr, lens[i]);
    if (!rx_enqueue(r, addr, lens[i], fl)) continue;
    out_ok[i] = 1;
    ok_n++;
  }
  return ok_n;
}

uint32_t bng_ring_frame_free_batch(bng_ring *r, const uint64_t *addrs,
                                   uint32_t n) {
  uint32_t freed = 0;
  for (uint32_t i = 0; i < n; i++) {
    if (addrs[i] >= r->umem_size) {
      r->stats.bad_desc++;
      continue;
    }
    recycle(r, addrs[i]);
    freed++;
  }
  return freed;
}

int bng_ring_rx_push(bng_ring *r, const uint8_t *data, uint32_t len,
                     uint32_t flags) {
  if (len > r->frame_size) {
    r->stats.bad_desc++;
    return -1;
  }
  uint64_t addr = bng_ring_rx_reserve(r);
  if (addr == UINT64_MAX) return -1;
  memcpy(r->umem + addr, data, len);
  return bng_ring_rx_submit(r, addr, len, flags);
}

static void stage_frame(bng_ring *r, uint8_t *out, uint32_t *out_len,
                        uint32_t *out_flags, uint32_t row, uint32_t slot,
                        const bng_desc &d) {
  uint32_t copy = d.len < slot ? d.len : slot;
  memcpy(out + static_cast<size_t>(row) * slot, r->umem + d.addr, copy);
  if (copy < slot)
    memset(out + static_cast<size_t>(row) * slot + copy, 0, slot - copy);
  out_len[row] = copy;
  out_flags[row] = d.flags;
}

uint32_t bng_batch_assemble(bng_ring *r, uint8_t *out, uint32_t *out_len,
                            uint32_t *out_flags, uint32_t max_batch,
                            uint32_t slot) {
  if (r->inflight_count >= bng_ring::MAX_INFLIGHT) return 0; /* windows full */
  if (max_batch > r->inflight_cap) max_batch = r->inflight_cap;
  uint32_t tail =
      (r->inflight_head + r->inflight_count) % bng_ring::MAX_INFLIGHT;
  uint32_t n = 0;
  bng_desc d;
  /* round-robin over shard queues so no shard starves (n_shards==1 is
   * the plain single-queue drain) */
  uint32_t idle = 0;
  for (uint32_t s = 0; n < max_batch && idle < r->n_shards;
       s = (s + 1) % r->n_shards) {
    if (!r->rxq[s].pop(&d)) {
      idle++;
      continue;
    }
    idle = 0;
    stage_frame(r, out, out_len, out_flags, n, slot, d);
    r->inflight[tail][n] = d;
    n++;
  }
  if (n == 0) return 0; /* empty assemble opens no window */
  r->inflight_n[tail] = n;
  r->inflight_count++;
  r->stats.rx += n;
  return n;
}

uint32_t bng_batch_assemble_sharded(bng_ring *r, uint8_t *out,
                                    uint32_t *out_len, uint32_t *out_flags,
                                    uint32_t b_per_shard, uint32_t slot) {
  if (r->inflight_count >= bng_ring::MAX_INFLIGHT) return 0; /* windows full */
  uint32_t total = r->n_shards * b_per_shard;
  if (b_per_shard == 0 || total > r->inflight_cap) return 0;
  uint32_t tail =
      (r->inflight_head + r->inflight_count) % bng_ring::MAX_INFLIGHT;
  uint32_t got = 0;
  bng_desc d;
  for (uint32_t s = 0; s < r->n_shards; s++) {
    for (uint32_t k = 0; k < b_per_shard; k++) {
      uint32_t row = s * b_per_shard + k;
      if (r->rxq[s].pop(&d)) {
        stage_frame(r, out, out_len, out_flags, row, slot, d);
        r->inflight[tail][row] = d;
        got++;
      } else {
        /* padding lane: zeroed so stale caller-buffer bytes can never be
         * parsed as a packet; complete() skips it via the addr marker */
        memset(out + static_cast<size_t>(row) * slot, 0, slot);
        out_len[row] = 0;
        out_flags[row] = 0;
        r->inflight[tail][row] = bng_desc{UINT64_MAX, 0, 0};
      }
    }
  }
  if (got == 0) return 0; /* nothing pending: no window opened */
  r->inflight_n[tail] = total;
  r->inflight_count++;
  r->stats.rx += got;
  return got;
}

int bng_batch_complete(bng_ring *r, const uint8_t *verdict,
                       const uint8_t *out, const uint32_t *out_len,
                       uint32_t n, uint32_t slot) {
  /* retires the OLDEST outstanding batch; n must match its size */
  uint32_t head = r->inflight_head;
  if (r->inflight_count == 0 || n != r->inflight_n[head] ||
      n > r->inflight_cap)
    return -1;
  for (uint32_t i = 0; i < n; i++) {
    bng_desc d = r->inflight[head][i];
    if (d.addr == UINT64_MAX) continue; /* sharded-assemble padding lane */
    uint8_t v = verdict[i];
    if (v == BNG_VERDICT_TX || v == BNG_VERDICT_FWD) {
      /* device rewrote the packet: copy staged bytes back over the frame.
       * Clamp to the chunk ROOM — a headroom-offset descriptor
       * (rx_submit_batch) owns only frame_size - off bytes of its chunk */
      uint32_t room =
          r->frame_size - static_cast<uint32_t>(d.addr % r->frame_size);
      uint32_t len = out_len[i];
      if (len > room) len = room;
      if (out) {
        memcpy(r->umem + d.addr, out + static_cast<size_t>(i) * slot,
               len < slot ? len : slot);
      }
      d.len = len;
      Ring &dst = (v == BNG_VERDICT_TX) ? r->tx : r->fwd;
      if (dst.push(d)) {
        if (v == BNG_VERDICT_TX) r->stats.tx++;
        else r->stats.fwd++;
      } else {
        r->stats.tx_full++;
        recycle(r, d.addr);
      }
    } else if (v == BNG_VERDICT_PASS) {
      if (r->slow.push(d)) r->stats.slow++;
      else {
        r->stats.tx_full++;
        recycle(r, d.addr);
      }
    } else { /* DROP (and any unknown verdict fails closed) */
      r->stats.drop++;
      recycle(r, d.addr);
    }
  }
  r->inflight_n[head] = 0;
  r->inflight_head = (head + 1) % bng_ring::MAX_INFLIGHT;
  r->inflight_count--;
  return 0;
}

/* A host-held frame onto one of the two output rings: a fresh UMEM frame,
 * the bytes copied in, queued where complete() queues a lane of that
 * verdict. */
static int inject(bng_ring *r, Ring &dst, uint64_t &stat,
                  const uint8_t *data, uint32_t len, uint32_t flags) {
  if (len > r->frame_size) {
    r->stats.bad_desc++;
    return -1;
  }
  bng_desc d;
  if (!r->fill.pop(&d)) {
    r->stats.fill_empty++;
    return -1;
  }
  memcpy(r->umem + d.addr, data, len);
  d.len = len;
  d.flags = flags;
  if (!dst.push(d)) {
    r->stats.tx_full++;
    r->fill.push(d);
    return -1;
  }
  stat++;
  return 0;
}

int bng_ring_tx_inject(bng_ring *r, const uint8_t *data, uint32_t len,
                       uint32_t flags) {
  return inject(r, r->tx, r->stats.tx, data, len, flags);
}

int bng_ring_fwd_inject(bng_ring *r, const uint8_t *data, uint32_t len,
                        uint32_t flags) {
  return inject(r, r->fwd, r->stats.fwd, data, len, flags);
}

/* Descriptor-based output pops for the AF_XDP wire: the frame STAYS in
 * UMEM (the kernel reads it directly for TX); the caller returns it to
 * the fill pool with bng_ring_frame_free after the completion ring
 * reports it sent. The copying *_pop variants below remain for
 * non-UMEM consumers (slow path, tests). */
static int pop_desc_from(bng_ring *r, Ring &ring, uint64_t *addr,
                         uint32_t *len, uint32_t *flags) {
  bng_desc d;
  if (!ring.pop(&d)) return 0;
  (void)r;
  *addr = d.addr;
  *len = d.len;
  if (flags) *flags = d.flags;
  return 1;
}

int bng_ring_tx_pop_desc(bng_ring *r, uint64_t *addr, uint32_t *len,
                         uint32_t *flags) {
  return pop_desc_from(r, r->tx, addr, len, flags);
}
int bng_ring_fwd_pop_desc(bng_ring *r, uint64_t *addr, uint32_t *len,
                          uint32_t *flags) {
  return pop_desc_from(r, r->fwd, addr, len, flags);
}

uint32_t bng_ring_out_pop_desc_batch(bng_ring *r, uint64_t *addrs,
                                     uint32_t *lens, uint32_t cap) {
  uint32_t n = 0;
  bng_desc d;
  /* tx drains first, then fwd — the scalar pump's per-frame pop order */
  while (n < cap && r->tx.pop(&d)) {
    addrs[n] = d.addr;
    lens[n] = d.len;
    n++;
  }
  while (n < cap && r->fwd.pop(&d)) {
    addrs[n] = d.addr;
    lens[n] = d.len;
    n++;
  }
  return n;
}

int bng_ring_frame_free(bng_ring *r, uint64_t addr) {
  if (!valid_addr(r, addr)) {
    r->stats.bad_desc++;
    return -1;
  }
  bng_desc d{addr, 0, 0};
  r->fill.push(d);
  return 0;
}

static int pop_from(bng_ring *r, Ring &ring, uint8_t *buf, uint32_t cap,
                    uint32_t *flags) {
  bng_desc d;
  if (!ring.pop(&d)) return 0;
  int rc;
  if (d.len <= cap) {
    memcpy(buf, r->umem + d.addr, d.len);
    rc = static_cast<int>(d.len);
  } else {
    rc = -1;
  }
  if (flags) *flags = d.flags;
  recycle(r, d.addr);
  return rc;
}

int bng_ring_tx_pop(bng_ring *r, uint8_t *buf, uint32_t cap,
                    uint32_t *flags) {
  return pop_from(r, r->tx, buf, cap, flags);
}
int bng_ring_fwd_pop(bng_ring *r, uint8_t *buf, uint32_t cap,
                     uint32_t *flags) {
  return pop_from(r, r->fwd, buf, cap, flags);
}
int bng_ring_slow_pop(bng_ring *r, uint8_t *buf, uint32_t cap,
                      uint32_t *flags) {
  return pop_from(r, r->slow, buf, cap, flags);
}

uint32_t bng_ring_rx_pending(bng_ring *r) {
  uint32_t sum = 0;
  for (uint32_t s = 0; s < r->n_shards; s++) sum += r->rxq[s].pending();
  return sum;
}
uint32_t bng_ring_shard_rx_pending(bng_ring *r, uint32_t shard) {
  return shard < r->n_shards ? r->rxq[shard].pending() : 0;
}
uint32_t bng_ring_tx_pending(bng_ring *r) { return r->tx.pending(); }
uint32_t bng_ring_fwd_pending(bng_ring *r) { return r->fwd.pending(); }
uint32_t bng_ring_slow_pending(bng_ring *r) { return r->slow.pending(); }
uint32_t bng_ring_free_frames(bng_ring *r) { return r->fill.pending(); }

void bng_ring_get_stats(bng_ring *r, bng_ring_stats *out) {
  *out = r->stats;
}

/* Move up to budget frames per direction between two rings' output sides
 * and the peer's RX. TX and FWD both land on the peer wire (a loopback
 * cable has one far end). */
static uint32_t pump_dir(bng_ring *src, bng_ring *dst, uint32_t budget) {
  uint32_t moved = 0;
  bng_desc d;
  while (moved < budget) {
    bool got = src->tx.pop(&d);
    if (!got) got = src->fwd.pop(&d);
    if (!got) break;
    /* flags flip: frames leaving the access side arrive at the core side.
     * The stale direction-specific DHCP-control bit needs no handling
     * here: rx_submit clears and re-derives it authoritatively for every
     * submitted frame. */
    uint32_t fl = d.flags ^ BNG_DESC_F_FROM_ACCESS;
    bng_ring_rx_push(dst, src->umem + d.addr, d.len, fl);
    recycle(src, d.addr);
    moved++;
  }
  return moved;
}

int bng_wire_pump(bng_ring *a, bng_ring *b, uint32_t budget) {
  uint32_t m = pump_dir(a, b, budget);
  m += pump_dir(b, a, budget);
  return static_cast<int>(m);
}

uint32_t bng_abi_desc_size(void) { return sizeof(bng_desc); }
uint32_t bng_abi_desc_addr_off(void) { return offsetof(bng_desc, addr); }
uint32_t bng_abi_desc_len_off(void) { return offsetof(bng_desc, len); }
uint32_t bng_abi_desc_flags_off(void) { return offsetof(bng_desc, flags); }
uint32_t bng_abi_stats_size(void) { return sizeof(bng_ring_stats); }
uint32_t bng_abi_version(void) { return 5; }

} /* extern "C" */
