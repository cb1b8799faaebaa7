// Native smoke test for the lock-free ring + send lease, sanitizer-ready.
//
// Built by tools/check.sh (direct g++) and by CMake (`ring_smoke` target),
// with or without TPURPC_SANITIZE={address,thread,undefined}. Under TSan the
// cross-thread test drives the exact producer/consumer protocol the Python
// pair runs over shm: plain data stores ordered by release/acquire fences
// plus the __atomic credit/waiter words. TSan's happens-before engine cannot
// see fence-ordered plain stores (that direction is covered by the
// exhaustive model checker, tpurpc/analysis/ringcheck.py, and suppressed in
// native/sanitize/tsan.supp); everything else — the credit word handshake,
// the lease bookkeeping, init/teardown — is checked for real.
//
//   g++ -std=c++17 -O1 -g -fsanitize=thread native/src/ring.cc \
//       native/test/ring_smoke.cc -o ring_smoke -lpthread
//   TSAN_OPTIONS=suppressions=native/sanitize/tsan.supp ./ring_smoke

#include <sched.h>

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "../src/tpr_obs.h"
#include "../src/tpr_rdv.h"

// Most of the ring ABI comes in through ring_transport.h (via tpr_rdv.h);
// these three are exported by ring.cc but not declared there.
extern "C" {
int tpr_abi_version();
uint64_t tpr_ring_readable(const uint8_t* ring, uint64_t cap, uint64_t head,
                           uint64_t msg_len, uint64_t msg_read, uint64_t seq);
uint64_t tpr_send_fast(uint8_t* ring, uint64_t cap, uint64_t* tail,
                       uint64_t* seq, const uint8_t* status_addr,
                       uint64_t* remote_head, const uint8_t* peer_rxwait_addr,
                       const uint8_t* const* segs, const uint64_t* lens,
                       uint32_t nsegs, uint64_t chunk_size, int* notify_out);
}

#define CHECK(cond)                                                     \
  do {                                                                  \
    if (!(cond)) {                                                      \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__,      \
                   #cond);                                              \
      std::exit(1);                                                     \
    }                                                                   \
  } while (0)

namespace {

constexpr uint64_t kCap = 4096;

// single-thread framing roundtrip: writev -> has_message -> read_into
void test_roundtrip() {
  std::vector<uint8_t> ring(kCap, 0);
  uint64_t tail = 0, wseq = 0;
  uint64_t head = 0, mlen = 0, mread = 0, consumed = 0, rseq = 0;

  uint8_t a[100], b[33];
  std::memset(a, 0xA1, sizeof(a));
  std::memset(b, 0xB2, sizeof(b));
  const uint8_t* segs[2] = {a, b};
  uint64_t lens[2] = {sizeof(a), sizeof(b)};
  CHECK(tpr_ring_writev(ring.data(), kCap, &tail, /*remote_head=*/0, segs,
                        lens, 2, &wseq) == sizeof(a) + sizeof(b));
  CHECK(tpr_ring_has_message(ring.data(), kCap, head, mlen, rseq) == 1);
  CHECK(tpr_ring_readable(ring.data(), kCap, head, mlen, mread, rseq) ==
        sizeof(a) + sizeof(b));

  uint8_t out[256];
  uint64_t n = tpr_ring_read_into(ring.data(), kCap, &head, &mlen, &mread,
                                  out, sizeof(out), &consumed, &rseq);
  CHECK(n == sizeof(a) + sizeof(b));
  for (size_t i = 0; i < sizeof(a); ++i) CHECK(out[i] == 0xA1);
  for (size_t i = 0; i < sizeof(b); ++i) CHECK(out[sizeof(a) + i] == 0xB2);
  CHECK(rseq == 1 && head == tail);
}

// lease: reserve -> fill segments in place -> commit -> read
void test_lease() {
  std::vector<uint8_t> ring(kCap, 0);
  uint64_t tail = 0, wseq = 0;
  uint64_t head = 0, mlen = 0, mread = 0, consumed = 0, rseq = 0;
  CHECK(tpr_ring_max_payload(kCap) == kCap - 24);

  // park the cursors near the end so the reserve WRAPS (two segments)
  uint64_t pre = kCap - 64;  // 8-aligned
  tail = head = pre;
  uint8_t *p1, *p2;
  uint64_t l1, l2;
  uint64_t want = 120;
  CHECK(tpr_ring_reserve(ring.data(), kCap, tail, /*remote_head=*/head, want,
                         &p1, &l1, &p2, &l2) == 1);
  CHECK(l1 + l2 == want && l2 > 0);  // wrapped
  std::memset(p1, 0xC3, l1);
  std::memset(p2, 0xC3, l2);
  // not visible until commit
  CHECK(tpr_ring_has_message(ring.data(), kCap, head, 0, rseq) == 0);
  tpr_ring_commit(ring.data(), kCap, &tail, want, &wseq);
  CHECK(tpr_ring_has_message(ring.data(), kCap, head, 0, rseq) == 1);
  uint8_t out[256];
  CHECK(tpr_ring_read_into(ring.data(), kCap, &head, &mlen, &mread, out,
                           sizeof(out), &consumed, &rseq) == want);
  for (uint64_t i = 0; i < want; ++i) CHECK(out[i] == 0xC3);
}

// two threads, full credit protocol: producer writes via tpr_send_fast
// (credit fold + chunked encode + notify decision), consumer drains and
// publishes its head into the shared status word — the exact shm protocol.
void test_spsc_threads() {
  std::vector<uint8_t> ring(256, 0);  // small: forces wraps + credit stalls
  const uint64_t cap = 256;
  // producer-side "status page": the consumer one-sided-writes its head at
  // +0; the consumer's page carries the read-waiter word at +64.
  alignas(64) static uint8_t prod_status[128];
  alignas(64) static uint8_t cons_status[128];
  std::memset(prod_status, 0, sizeof(prod_status));
  std::memset(cons_status, 0, sizeof(cons_status));

  const int kMsgs = 2000;
  const uint64_t kLen = 48;

  std::thread producer([&] {
    uint64_t tail = 0, seq = 0, remote_head = 0;
    uint8_t payload[kLen];
    for (int m = 0; m < kMsgs; ++m) {
      std::memset(payload, m & 0xFF, sizeof(payload));
      const uint8_t* segs[1] = {payload};
      uint64_t lens[1] = {kLen};
      uint64_t sent = 0;
      while (sent < kLen) {
        int notify = 0;
        const uint8_t* seg0 = payload + sent;
        const uint8_t* s2[1] = {seg0};
        uint64_t l2[1] = {kLen - sent};
        uint64_t got = tpr_send_fast(ring.data(), cap, &tail, &seq,
                                     prod_status, &remote_head,
                                     cons_status + 64, s2, l2, 1,
                                     /*chunk=*/kLen, &notify);
        sent += got;
        if (got == 0) sched_yield();  // stalled for credits
      }
      (void)segs;
      (void)lens;
    }
  });

  std::thread consumer([&] {
    uint64_t head = 0, mlen = 0, mread = 0, consumed = 0, seq = 0;
    uint8_t buf[4096];
    uint64_t total = 0, expect = uint64_t(kMsgs) * kLen;
    uint64_t msg_byte = 0;  // cursor within the current logical message
    while (total < expect) {
      uint64_t n = tpr_ring_read_into(ring.data(), cap, &head, &mlen, &mread,
                                      buf, sizeof(buf), &consumed, &seq);
      CHECK(n != ~0ULL);
      if (n == 0) {
        // advertise the read-waiter word like a parking consumer would,
        // then retract it — exercises the sleep-protocol words under TSan
        tpr_store_u64_seqcst(cons_status + 64, 1);
        if (tpr_ring_has_message(ring.data(), cap, head, mlen, seq) == 0)
          sched_yield();
        tpr_store_u64_seqcst(cons_status + 64, 0);
        continue;
      }
      // verify contents: bytes of message m are (m & 0xFF); messages may
      // arrive split across drains (chunked sends), so track a byte cursor
      for (uint64_t i = 0; i < n; ++i) {
        uint64_t m = (total + i) / kLen;
        CHECK(buf[i] == uint8_t(m & 0xFF));
        (void)msg_byte;
      }
      total += n;
      // publish credits: one-sided store of our head into the producer's
      // status page (+0), release-ordered by the seq_cst store
      tpr_store_u64_seqcst(prod_status, head);
    }
    CHECK(tpr_load_u64_fenced(prod_status) == head);
  });

  producer.join();
  consumer.join();
}

// tpurpc-xray: the obs ring's seqlock protocol — wrap, torn-read
// detection, concurrent writers — exercised for real under TSan (record
// payloads are atomic word stores, so no suppressions are needed here).
void test_obs_ring() {
  if (!tpr_obs_enabled()) {
    std::puts("ring_smoke: native obs disabled by env, skipping");
    return;
  }
  tpr_obs_reset();
  const uint32_t cap = tpr_obs_capacity();
  CHECK(cap >= 64);
  CHECK(tpr_obs_layout_version() == 1);
  CHECK(tpr_obs_shm_name()[0] != '\0');

  // tag intern: stable, idempotent, readable back
  uint16_t t1 = tpr_obs_tag_for("smoke:a");
  uint16_t t2 = tpr_obs_tag_for("smoke:b");
  CHECK(t1 != 0 && t2 != 0 && t1 != t2);
  CHECK(tpr_obs_tag_for("smoke:a") == t1);
  char nm[64];
  CHECK(tpr_obs_tag_name(t1, nm, sizeof nm) == 7);
  CHECK(std::strcmp(nm, "smoke:a") == 0);

  // basic emit/read roundtrip: the record decodes whole
  tpr_obs_emit(tpr_obs::kEvPinWaitBegin, t1, 123, -456);
  std::vector<uint8_t> buf((size_t)cap * tpr_obs::kRecordBytes);
  int n = tpr_obs_read(buf.data(), (int)cap);
  CHECK(n == 1);
  uint64_t w[4];
  std::memcpy(w, buf.data(), sizeof w);
  CHECK((w[1] & 0xFFFF) == tpr_obs::kEvPinWaitBegin);
  CHECK(((w[1] >> 16) & 0xFFFF) == t1);
  CHECK((int64_t)w[2] == 123 && (int64_t)w[3] == -456);
  CHECK(w[0] != 0);  // CLOCK_MONOTONIC stamp

  // wrap: capacity + 37 emits leave exactly `capacity` readable records,
  // all from the newest window (a1 encodes the emission index)
  tpr_obs_reset();
  const uint64_t total = (uint64_t)cap + 37;
  for (uint64_t i = 0; i < total; ++i)
    tpr_obs_emit(tpr_obs::kEvPinWaitEnd, t1, (int64_t)i, 0);
  n = tpr_obs_read(buf.data(), (int)cap);
  CHECK(n == (int)cap);
  for (int i = 0; i < n; ++i) {
    std::memcpy(w, buf.data() + (size_t)i * tpr_obs::kRecordBytes, sizeof w);
    CHECK(w[2] >= total - cap && w[2] < total);
  }

  // concurrent writers + one racing reader: every record the reader
  // accepts must be internally whole (each writer stamps a1 == ~a2, so
  // any torn mix of two records breaks the invariant) — the per-slot
  // seqlock recheck is the only thing standing between this and a
  // corrupt read.
  tpr_obs_reset();
  std::atomic<bool> stop{false};
  std::atomic<uint64_t> torn{0};
  std::thread obs_reader([&] {
    std::vector<uint8_t> rb((size_t)cap * tpr_obs::kRecordBytes);
    while (!stop.load()) {
      int k = tpr_obs_read(rb.data(), (int)cap);
      for (int i = 0; i < k; ++i) {
        uint64_t v[4];
        std::memcpy(v, rb.data() + (size_t)i * tpr_obs::kRecordBytes,
                    sizeof v);
        if (v[2] != ~v[3]) torn.fetch_add(1);
      }
    }
  });
  const int kWriters = 4;
  const uint64_t kPerWriter = 20000;
  std::vector<std::thread> obs_writers;
  for (int wi = 0; wi < kWriters; ++wi) {
    obs_writers.emplace_back([&, wi] {
      for (uint64_t i = 0; i < kPerWriter; ++i) {
        uint64_t v = ((uint64_t)(wi + 1) << 32) | i;
        tpr_obs_emit(tpr_obs::kEvDlvStallBegin, t2, (int64_t)v,
                     (int64_t)~v);
      }
    });
  }
  for (auto &th : obs_writers) th.join();
  stop.store(true);
  obs_reader.join();
  CHECK(torn.load() == 0);

  // after the dust settles every slot holds one whole record, and the
  // emitted counter saw every write (wraps overwrite, never drop)
  n = tpr_obs_read(buf.data(), (int)cap);
  CHECK(n == (int)cap);
  for (int i = 0; i < n; ++i) {
    std::memcpy(w, buf.data() + (size_t)i * tpr_obs::kRecordBytes, sizeof w);
    CHECK(w[2] == ~w[3]);
  }
  uint64_t mets[tpr_obs::kNumMetrics] = {0};
  tpr_obs_counters(mets, (int)tpr_obs::kNumMetrics);
  CHECK(mets[tpr_obs::kMetEmitted] == kWriters * kPerWriter);
  tpr_obs_reset();
}

// Loopback harness for the rendezvous ladder: two Links wired back to
// back, framed control frames delivered synchronously (each side's
// send_frame calls the peer's on_frame and advances both frame counters,
// keeping the ctrl-ring ordering gate consistent), claim waits pumped by
// draining our own rx ring — the inline-read discipline in miniature.
struct RdvPeer {
  tpr_rdv::Link link;
  RdvPeer *peer = nullptr;
  std::vector<uint8_t> delivered;
  uint8_t last_flags = 0;

  explicit RdvPeer(const char *name) : link(name) {
    link.send_frame = [this](uint8_t type, uint32_t sid,
                             const std::string &p) {
      link.frames_sent.fetch_add(1, std::memory_order_release);
      peer->link.on_frame(type, sid,
                          reinterpret_cast<const uint8_t *>(p.data()),
                          p.size());
      peer->link.frames_dispatched.fetch_add(1, std::memory_order_release);
      peer->link.ctrl_drain();  // post-dispatch gate lift, as the conns do
      return true;
    };
    link.deliver = [this](uint32_t sid, uint8_t flags, uint8_t *data,
                          size_t len) {
      (void)sid;
      delivered.assign(data, data + len);
      last_flags = flags;
      CHECK(tpr_rdv::settle(data));  // region pointer, settled exactly once
    };
    link.wake = [] {};
    // The pump stands in for BOTH dispatch loops: the real conns poll
    // their rx rings while hot; a single-threaded harness has to drain
    // the peer's ring too or ring-borne ops would strand.
    link.pump = [this](const std::function<bool()> &pred,
                       std::chrono::steady_clock::time_point dl) {
      while (!pred() && std::chrono::steady_clock::now() < dl) {
        int n = link.ctrl_drain();
        if (peer) n += peer->link.ctrl_drain();
        if (n == 0) sched_yield();
      }
    };
  }
};

void test_rdv_loopback() {
  if (!tpr_rdv::enabled() || !tpr_rdv::ctrl_enabled()) {
    std::puts("ring_smoke: rdv disabled by env, skipping ladder");
    return;
  }
  RdvPeer a("cli"), b("srv");
  a.peer = &b;
  b.peer = &a;
  // capability hello both ways (the PING payloads the conns exchange)
  std::string ha = a.link.hello_payload(), hb = b.link.hello_payload();
  CHECK(b.link.maybe_hello(reinterpret_cast<const uint8_t *>(ha.data()),
                           ha.size()));
  CHECK(a.link.maybe_hello(reinterpret_cast<const uint8_t *>(hb.data()),
                           hb.size()));
  CHECK(a.link.negotiated.load() && b.link.negotiated.load());
  // a plain PING must NOT negotiate (un-negotiated peers stay framed)
  tpr_rdv::Link lone("lone");
  CHECK(!lone.maybe_hello(reinterpret_cast<const uint8_t *>("p"), 1));
  CHECK(!lone.negotiated.load());
  CHECK(!lone.eligible(tpr_rdv::min_bytes()));

  // sub-threshold payloads are never eligible — they stay framed
  CHECK(!a.link.eligible(tpr_rdv::min_bytes() - 1));
  CHECK(a.link.eligible(tpr_rdv::min_bytes()));

  // the ladder: one transfer per size class, byte-exact, region-settled
  const uint64_t before_sent =
      tpr_rdv::g_counters[tpr_rdv::kCtrRdvSent].load();
  const size_t sizes[] = {size_t(tpr_rdv::min_bytes()), 1u << 20,
                          (1u << 22) + 5};  // odd tail crosses class pad
  uint64_t total_bytes = 0;
  for (size_t n : sizes) {
    std::vector<uint8_t> payload(n);
    for (size_t i = 0; i < n; ++i)
      payload[i] = uint8_t((i * 31 + n) & 0xFF);
    b.delivered.clear();
    CHECK(a.link.send_message(7, /*flags=*/0x01, payload.data(), n));
    b.link.ctrl_drain();  // the receiver's hot dispatch poll
    CHECK(b.delivered.size() == n);
    CHECK(std::memcmp(b.delivered.data(), payload.data(), n) == 0);
    CHECK(b.last_flags == 0x01);
    total_bytes += n;
  }
  CHECK(tpr_rdv::g_counters[tpr_rdv::kCtrRdvSent].load() ==
        before_sent + 3);

  // ctrl-ring discipline: the ladder's control ops moved as ring records
  // (the kicks that did fire targeted a parked consumer). Steady state —
  // repeat transfers with both consumers hot — posts records with ZERO
  // framed control ops and ZERO kicks: the zero-wakeup acceptance bar.
  CHECK(tpr_rdv::g_counters[tpr_rdv::kCtrCtrlRecords].load() > 0);
  a.link.ctrl_drain();
  b.link.ctrl_drain();
  const uint64_t frames0 =
      tpr_rdv::g_counters[tpr_rdv::kCtrCtrlFrames].load();
  const uint64_t kicks0 = tpr_rdv::g_counters[tpr_rdv::kCtrCtrlKicks].load();
  for (int rep = 0; rep < 4; ++rep) {
    std::vector<uint8_t> payload(1u << 20, uint8_t(rep));
    b.delivered.clear();
    CHECK(a.link.send_message(9, 0, payload.data(), payload.size()));
    b.link.ctrl_drain();
    CHECK(b.delivered.size() == payload.size());
  }
  CHECK(tpr_rdv::g_counters[tpr_rdv::kCtrCtrlFrames].load() == frames0);
  CHECK(tpr_rdv::g_counters[tpr_rdv::kCtrCtrlKicks].load() == kicks0);

  // park/kick: a parked consumer's producer goes framed with a CTRL_KICK
  // (posted record + kick frame), and the record still lands in order
  a.link.ctrl_park();
  {
    std::vector<uint8_t> payload(1u << 20, 0x5A);
    b.delivered.clear();
    CHECK(a.link.send_message(11, 0, payload.data(), payload.size()));
    b.link.ctrl_drain();
    CHECK(b.delivered.size() == payload.size());
  }
  a.link.close();
  b.link.close();
  lone.close();
}

// A dead link refuses new sends (framed fallback) instead of hanging —
// the never-hang half of the fallback contract, claim waiters included.
void test_rdv_closed_link_falls_back() {
  if (!tpr_rdv::enabled() || !tpr_rdv::ctrl_enabled()) return;
  RdvPeer a("cli2"), b("srv2");
  a.peer = &b;
  b.peer = &a;
  std::string ha = a.link.hello_payload(), hb = b.link.hello_payload();
  b.link.maybe_hello(reinterpret_cast<const uint8_t *>(ha.data()),
                     ha.size());
  a.link.maybe_hello(reinterpret_cast<const uint8_t *>(hb.data()),
                     hb.size());
  b.link.close();  // peer dies: its on_frame goes quiet
  b.link.ctrl_drain();
  std::vector<uint8_t> payload(1u << 20, 0x77);
  auto t0 = std::chrono::steady_clock::now();
  // the peer never claims; send_message must return false (framed
  // fallback) within the claim timeout, never hang
  CHECK(!a.link.send_message(13, 0, payload.data(), payload.size()));
  double waited = std::chrono::duration<double>(
                      std::chrono::steady_clock::now() - t0).count();
  CHECK(waited < tpr_rdv::claim_timeout_s() + 2.0);
  a.link.close();
}

}  // namespace

int main() {
  CHECK(tpr_abi_version() == 9);
  test_roundtrip();
  test_lease();
  test_spsc_threads();
  test_obs_ring();
  test_rdv_loopback();
  test_rdv_closed_link_falls_back();
  std::puts("ring_smoke: OK");
  return 0;
}
