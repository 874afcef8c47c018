// Unit tests for the C++ common layer (no gtest in the image — plain
// CHECK macros; non-zero exit on failure).
#include <signal.h>
#include <stdlib.h>
#include <sys/stat.h>
#include <sys/wait.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cassert>
#include <cstdio>
#include <cstring>
#include <string>
#include <thread>
#include <vector>

#include "common/bytes.h"
#include "common/eventlog.h"
#include "common/fileid.h"
#include "common/healthmon.h"
#include "common/heatsketch.h"
#include "common/ini.h"
#include "common/lockrank.h"
#include "common/metrog.h"
#include "common/net.h"
#include "common/protocol_gen.h"
#include "common/sloeval.h"
#include "common/stats.h"
#include "common/profiler.h"
#include "common/threadreg.h"
#include "common/trace.h"
#include "common/workers.h"

static int g_failures = 0;

#define CHECK(cond)                                                   \
  do {                                                                \
    if (!(cond)) {                                                    \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
      ++g_failures;                                                   \
    }                                                                 \
  } while (0)

#define CHECK_EQ(a, b) CHECK((a) == (b))

using namespace fdfs;

static void TestEndian() {
  uint8_t buf[8];
  PutInt64BE(0x0102030405060708LL, buf);
  CHECK_EQ(buf[0], 1);
  CHECK_EQ(buf[7], 8);
  CHECK_EQ(GetInt64BE(buf), 0x0102030405060708LL);
  PutInt64BE(-1, buf);
  CHECK_EQ(GetInt64BE(buf), -1);
}

static void TestBase64() {
  const uint8_t data[] = {0xDE, 0xAD, 0xBE, 0xEF, 0x00};
  std::string enc = Base64UrlEncode(data, sizeof(data));
  std::string dec;
  CHECK(Base64UrlDecode(enc, &dec));
  CHECK_EQ(dec.size(), sizeof(data));
  CHECK_EQ(std::memcmp(dec.data(), data, sizeof(data)), 0);
  CHECK(!Base64UrlDecode("a+b", &dec));  // '+' not in url-safe alphabet
  CHECK(!Base64UrlDecode("abcde", &dec));  // impossible length (5 % 4 == 1)
}

// The one-table loop Crc32 was until PR 34: the reference its loops are
// held to.
static uint32_t Crc32OneTable(const uint8_t* p, size_t len, uint32_t seed) {
  static uint32_t table[256];
  if (table[1] == 0) {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      table[i] = c;
    }
  }
  uint32_t c = seed ^ 0xFFFFFFFFu;
  for (size_t i = 0; i < len; ++i) c = table[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

static void TestCrc32() {
  // zlib golden: crc32(b"123456789") == 0xCBF43926
  CHECK_EQ(Crc32("123456789", 9), 0xCBF43926u);
  CHECK_EQ(Crc32("", 0), 0u);

  std::vector<Crc32Impl> impls = {Crc32Impl::kSliced};
  if (Crc32Chosen() == Crc32Impl::kFolded) impls.push_back(Crc32Impl::kFolded);
  std::vector<size_t> lengths;
  for (size_t n = 0; n <= 70; ++n) lengths.push_back(n);
  for (size_t n : {255u, 256u, 257u, 4095u, 4096u, 4097u, (1u << 20) + 3})
    lengths.push_back(n);
  std::vector<uint8_t> buf(lengths.back() + 8);
  uint32_t x = 34;
  for (auto& b : buf) {
    x = x * 1664525u + 1013904223u;
    b = static_cast<uint8_t>(x >> 24);
  }
  for (Crc32Impl impl : impls) {
    // Every length at every offset into the buffer, with and without a
    // register carried in.
    for (size_t n : lengths) {
      for (size_t off = 0; off < 8; ++off) {
        const uint8_t* p = buf.data() + off;
        CHECK_EQ(Crc32With(impl, p, n), Crc32OneTable(p, n, 0));
        CHECK_EQ(Crc32With(impl, p, n, 0xDEADBEEFu),
                 Crc32OneTable(p, n, 0xDEADBEEFu));
      }
    }
    // Chained calls split at every position give the whole's sum: of a
    // 64-byte string, and of one long enough that both parts fold.
    for (size_t total : {size_t{64}, size_t{300}}) {
      uint32_t whole = Crc32OneTable(buf.data(), total, 0);
      for (size_t cut = 0; cut <= total; ++cut) {
        uint32_t head = Crc32With(impl, buf.data(), cut);
        CHECK_EQ(Crc32With(impl, buf.data() + cut, total - cut, head), whole);
      }
    }
  }
  // The receive stage's shape: 256 KB pieces, whatever loop was chosen.
  uint32_t piecewise = 0;
  for (size_t off = 0; off < (1u << 20); off += 256 << 10)
    piecewise = Crc32(buf.data() + off, 256 << 10, piecewise);
  CHECK_EQ(piecewise, Crc32OneTable(buf.data(), 1u << 20, 0));
}

static void TestSha1() {
  CHECK_EQ(Sha1("abc", 3).Hex(),
           std::string("a9993e364706816aba3e25717850c26c9cd0d89d"));
  CHECK_EQ(Sha1("", 0).Hex(),
           std::string("da39a3ee5e6b4b0d3255bfef95601890afd80709"));
  // streamed == one-shot across buffer boundaries
  std::string big(1000, 'x');
  Sha1Stream s;
  s.Update(big.data(), 37);
  s.Update(big.data() + 37, 63);
  s.Update(big.data() + 100, 900);
  CHECK_EQ(s.Final().Hex(), Sha1(big.data(), big.size()).Hex());
}

static void TestFileId() {
  EncodeFileIdArgs a;
  a.group = "group1";
  a.store_path_index = 0;
  a.source_ip = PackIp("192.168.1.102");
  a.create_timestamp = 1406000000;
  a.file_size = 30790;
  a.crc32 = 0xFCEFEF3Cu;
  a.ext = "jpg";
  a.uniquifier = 42;
  auto id = EncodeFileId(a);
  CHECK(id.has_value());
  auto parts = DecodeFileId(*id);
  CHECK(parts.has_value());
  CHECK_EQ(parts->group, std::string("group1"));
  CHECK_EQ(UnpackIp(parts->source_ip), std::string("192.168.1.102"));
  CHECK_EQ(parts->create_timestamp, 1406000000u);
  CHECK_EQ(parts->file_size, 30790u);
  CHECK_EQ(parts->crc32, 0xFCEFEF3Cu);
  CHECK_EQ(parts->uniquifier, 42);
  CHECK(!parts->appender);
  CHECK_EQ(parts->FullId(), *id);

  // flags
  a.appender = true;
  auto id2 = EncodeFileId(a);
  auto p2 = DecodeFileId(*id2);
  CHECK(p2.has_value() && p2->appender);

  // tampering
  std::string bad = *id;
  bad[bad.size() - 5] = bad[bad.size() - 5] == 'A' ? 'B' : 'A';
  CHECK(!DecodeFileId(bad).has_value());

  // invalid encode args
  EncodeFileIdArgs e = a;
  e.group = "this-group-name-is-way-too-long";
  CHECK(!EncodeFileId(e).has_value());
  e = a;
  e.ext = "tar.gz";
  CHECK(!EncodeFileId(e).has_value());
  e = a;
  e.uniquifier = 0x1000;
  CHECK(!EncodeFileId(e).has_value());
}

static void TestLocalPath() {
  EncodeFileIdArgs a;
  a.group = "g";
  a.source_ip = PackIp("1.2.3.4");
  a.create_timestamp = 1;
  a.file_size = 2;
  a.crc32 = 3;
  a.ext = "txt";
  auto id = EncodeFileId(a);
  auto parts = DecodeFileId(*id);
  auto lp = LocalPath("/var/p0", parts->RemoteFilename());
  CHECK(lp.has_value());
  CHECK(lp->rfind("/var/p0/data/", 0) == 0);
  CHECK(!LocalPath("/var/p0", "M00/../../passwd").has_value());
  CHECK(!LocalPath("/var/p0", "M00/00/00/../../../etc/passwd").has_value());
  CHECK(!LocalPath("/var/p0", "no/such/shape/x").has_value());
}

static void TestIni() {
  IniConfig cfg;
  std::string err;
  CHECK(cfg.LoadString(
      "# comment\nport = 22122\ndisabled=false\n"
      "tracker_server = 10.0.0.1:22122\ntracker_server = 10.0.0.2:22122\n"
      "buff_size = 256KB\ninterval = 5m\n[section]\nname=x\n",
      &err));
  CHECK_EQ(cfg.GetInt("port", 0), 22122);
  CHECK(!cfg.GetBool("disabled", true));
  CHECK_EQ(cfg.GetAll("tracker_server").size(), 2u);
  CHECK_EQ(cfg.GetBytes("buff_size", 0), 256 * 1024);
  CHECK_EQ(cfg.GetSeconds("interval", 0), 300);
  CHECK_EQ(cfg.GetStr("name", ""), std::string("x"));
  CHECK(!cfg.Has("nope"));
  IniConfig inc;
  CHECK(!inc.LoadString("#include other.conf\n", &err));  // no base dir
}

static void TestProtocolConstants() {
  CHECK_EQ(static_cast<int>(TrackerCmd::kStorageJoin), 81);
  CHECK_EQ(static_cast<int>(TrackerCmd::kServiceQueryStoreWithoutGroupOne), 101);
  CHECK_EQ(static_cast<int>(StorageCmd::kUploadFile), 11);
  CHECK_EQ(static_cast<int>(StorageCmd::kResp), 100);
  CHECK_EQ(static_cast<int>(StorageCmd::kStat), 130);
  CHECK_EQ(static_cast<int>(TrackerCmd::kServerClusterStat), 95);
  CHECK_EQ(kHeaderSize, 10);
  // Beat-blob naming contract: one name per slot, the named headline
  // stats present (the Python side asserts the same list).
  CHECK_EQ(kBeatStatCount, 33);
  CHECK_EQ(std::string(kBeatStatNames[0]), std::string("total_upload"));
  CHECK_EQ(std::string(kBeatStatNames[17]),
           std::string("dedup_bytes_saved"));
  CHECK_EQ(std::string(kBeatStatNames[21]), std::string("sync_lag_s"));
  CHECK_EQ(std::string(kBeatStatNames[23]),
           std::string("recovery_chunks_fetched"));
  CHECK_EQ(std::string(kBeatStatNames[28]),
           std::string("rebalance_files_moved"));
  CHECK_EQ(std::string(kBeatStatNames[32]), std::string("rebalance_done"));
}

static void TestStatsRegistry() {
  StatsRegistry reg;
  reg.Counter("a.count")->fetch_add(3);
  CHECK_EQ(reg.Counter("a.count")->load(), 3);  // find-or-create finds
  reg.SetGauge("g", 42);
  reg.GaugeFn("g.fn", [] { return int64_t{7}; });
  StatHistogram* h = reg.Histogram("h", {10, 100, 1000});
  h->Observe(5);
  h->Observe(10);    // inclusive upper bound: first bucket
  h->Observe(11);    // second bucket
  h->Observe(5000);  // overflow
  CHECK_EQ(h->count(), 4);
  CHECK_EQ(h->sum(), 5 + 10 + 11 + 5000);
  CHECK_EQ(h->bucket_count(0), 2);
  CHECK_EQ(h->bucket_count(1), 1);
  CHECK_EQ(h->bucket_count(2), 0);
  CHECK_EQ(h->bucket_count(3), 1);
  std::string json = reg.Json();
  // Shape spot-checks (the full field-for-field check is the
  // cross-language golden test via `fdfs_codec stats-json`).
  CHECK(json.find("\"counters\":{\"a.count\":3}") != std::string::npos);
  CHECK(json.find("\"g\":42") != std::string::npos);
  CHECK(json.find("\"g.fn\":7") != std::string::npos);
  CHECK(json.find("\"bounds\":[10,100,1000]") != std::string::npos);
  CHECK(json.find("\"counts\":[2,1,0,1]") != std::string::npos);
  CHECK(json.find("\"sum\":5026") != std::string::npos);
}

static void TestTraceCtxWire() {
  // Wire layout golden: 8B trace_id + 4B parent + 4B flags, big-endian —
  // must match fastdfs_tpu.common.protocol.pack_trace_ctx byte-for-byte.
  const uint8_t raw[16] = {0x01, 0x02, 0x03, 0x04, 0x05, 0x06, 0x07, 0x08,
                           0xAA, 0xBB, 0xCC, 0xDD, 0x00, 0x00, 0x00, 0x03};
  TraceCtx c = ParseTraceCtx(raw);
  CHECK_EQ(c.trace_id, 0x0102030405060708ULL);
  CHECK_EQ(c.parent_span, 0xAABBCCDDu);
  CHECK_EQ(c.flags, 3u);
  CHECK(c.valid());
  uint8_t back[16];
  SerializeTraceCtx(c, back);
  CHECK_EQ(std::memcmp(raw, back, 16), 0);
  CHECK(!TraceCtx{}.valid());
  CHECK_EQ(static_cast<int>(StorageCmd::kTraceCtx),
           static_cast<int>(TrackerCmd::kTraceCtx));  // shared framing
  CHECK_EQ(static_cast<int>(StorageCmd::kTraceDump), 131);
  CHECK_EQ(static_cast<int>(TrackerCmd::kTraceDump), 96);
}

static void TestTraceRing() {
  TraceRing ring(4);
  uint32_t a = ring.NextSpanId(), b = ring.NextSpanId();
  CHECK(a != b && a != 0 && b != 0);
  CHECK(ring.NewTraceId() != ring.NewTraceId());
  for (int i = 0; i < 6; ++i) {  // wraps: 6 records into 4 slots
    TraceSpan s;
    s.trace_id = 0xABC0ULL + i;
    s.span_id = static_cast<uint32_t>(i + 1);
    s.start_us = 1000 + i;
    s.dur_us = 10;
    s.SetName(i % 2 ? "storage.recv" : "storage.upload_file");
    ring.Record(s);
  }
  CHECK_EQ(ring.recorded(), 6);
  CHECK_EQ(ring.dropped(), 2);
  std::string json = ring.Json("storage", 23000);
  CHECK(json.find("\"role\":\"storage\"") != std::string::npos);
  CHECK(json.find("\"port\":23000") != std::string::npos);
  // Oldest two overwritten; newest four present, sorted by start_us.
  CHECK(json.find("\"start_us\":1000,") == std::string::npos);
  CHECK(json.find("\"start_us\":1005,") != std::string::npos);
  size_t p2 = json.find("\"start_us\":1002");
  size_t p5 = json.find("\"start_us\":1005");
  CHECK(p2 != std::string::npos && p2 < p5);
  // Long names truncate, never overflow.
  TraceSpan longname;
  longname.trace_id = 1;
  longname.SetName("this-name-is-way-longer-than-the-forty-byte-span-field");
  CHECK_EQ(std::strlen(longname.name), sizeof(longname.name) - 1);
}

static void TestTraceRingThreaded() {
  // Lock-light claim: concurrent recorders + a dumping reader must be
  // data-race-free (tools/run_sanitizers.sh runs this under TSan).
  TraceRing ring(64);
  std::vector<std::thread> threads;
  for (int t = 0; t < 4; ++t) {
    threads.emplace_back([&ring, t] {
      for (int i = 0; i < 500; ++i) {
        TraceSpan s;
        s.trace_id = static_cast<uint64_t>(t) << 32 | i;
        s.span_id = ring.NextSpanId();
        s.start_us = i;
        s.dur_us = 1;
        s.SetName("storage.upload_file");
        ring.Record(s);
      }
    });
  }
  std::thread reader([&ring] {
    for (int i = 0; i < 50; ++i) (void)ring.Json("storage", 1);
  });
  for (auto& th : threads) th.join();
  reader.join();
  CHECK_EQ(ring.recorded(), 4 * 500);
  CHECK(ring.Json("storage", 1).find("\"spans\":[") != std::string::npos);
}

static void TestTraceCorrelator() {
  TraceCorrelator corr(2);
  TraceCtx c1{1, 10, 1}, c2{2, 20, 1}, c3{3, 30, 1}, out;
  corr.Put("M00/a", c1);
  corr.Put("M00/b", c2);
  corr.Put("M00/c", c3);  // evicts the oldest (M00/a)
  CHECK_EQ(corr.size(), 2u);
  CHECK(!corr.Take("M00/a", &out));
  CHECK(corr.Take("M00/b", &out));
  CHECK_EQ(out.trace_id, 2ULL);
  CHECK(!corr.Take("M00/b", &out));  // Take consumes
  CHECK(corr.Take("M00/c", &out));
  CHECK_EQ(corr.size(), 0u);
}

static void TestEventLog() {
  EventLog log(4);
  log.Record(EventSeverity::kWarn, "chunk.quarantined", "digest1", "spi=0");
  log.Record(EventSeverity::kInfo, "chunk.repaired", "digest1");
  std::string json = log.Json("storage", 23000);
  CHECK(json.find("\"role\":\"storage\"") != std::string::npos);
  CHECK(json.find("\"type\":\"chunk.quarantined\"") != std::string::npos);
  CHECK(json.find("\"severity\":\"warn\"") != std::string::npos);
  CHECK(json.find("\"seq\":1") != std::string::npos);
  CHECK_EQ(log.recorded(), 2);
  CHECK_EQ(log.dropped(), 0);
  // Ring wrap: capacity 4, record 6 — the oldest 2 are overwritten and
  // the dump holds exactly seqs 3..6 in order.
  for (int i = 0; i < 4; ++i)
    log.Record(EventSeverity::kError, "gc.sweep", "M00",
               "n=" + std::to_string(i));
  CHECK_EQ(log.recorded(), 6);
  CHECK_EQ(log.dropped(), 2);
  json = log.Json("storage", 23000);
  CHECK(json.find("\"seq\":1,") == std::string::npos);
  CHECK(json.find("\"seq\":3") != std::string::npos);
  CHECK(json.find("\"seq\":6") != std::string::npos);
  // Hostile bytes in key/detail must still serialize as valid JSON
  // (escaped), and over-long fields truncate instead of overflowing.
  EventLog esc(2);
  esc.Record(EventSeverity::kInfo, "config.anomaly", "a\"b\\c\nd",
             std::string(500, 'x'));
  json = esc.Json("tracker", 22122);
  CHECK(json.find("a\\\"b\\\\c\\nd") != std::string::npos);
  CHECK(json.find(std::string(127, 'x') + "\"") != std::string::npos);
}

static void TestEventLogThreaded() {
  // Lock-light claim: concurrent recorders + a dumping reader must be
  // data-race-free (tools/run_sanitizers.sh runs this under TSan) —
  // the flight-recorder twin of TestTraceRingThreaded.
  EventLog log(64);
  std::vector<std::thread> threads;
  for (int t = 0; t < 4; ++t) {
    threads.emplace_back([&log, t] {
      for (int i = 0; i < 500; ++i)
        log.Record(EventSeverity::kInfo, "request.slow",
                   "t" + std::to_string(t), "i=" + std::to_string(i));
    });
  }
  std::thread reader([&log] {
    for (int i = 0; i < 50; ++i) (void)log.Json("storage", 1);
  });
  for (auto& th : threads) th.join();
  reader.join();
  CHECK_EQ(log.recorded(), 4 * 500);
  CHECK(log.Json("storage", 1).find("\"events\":[") != std::string::npos);
}

static void TestEventLoopLagHook() {
  // The iteration hook must observe the time spent inside callbacks: a
  // deliberately-slow posted task shows up as loop lag >= its sleep.
  EventLoop loop;
  StatsRegistry reg;
  StatHistogram* lag = reg.Histogram("nio.loop_lag_us",
                                     StatsRegistry::LatencyBucketsUs());
  std::atomic<int64_t> dispatched{0};
  loop.set_iteration_hook([&](int64_t busy_us, int n_events) {
    lag->Observe(busy_us);
    dispatched.fetch_add(n_events);
  });
  loop.Post([] { usleep(20 * 1000); });
  loop.Post([&loop] { loop.Stop(); });
  loop.Run();
  CHECK(lag->count() >= 1);
  CHECK(lag->sum() >= 20000);  // the 20 ms stall is visible as lag
}

static void TestWorkerPoolQueueStats() {
  StatsRegistry reg;
  StatHistogram* wait = reg.Histogram("dio.queue_wait_us",
                                      StatsRegistry::LatencyBucketsUs());
  StatHistogram* service = reg.Histogram("dio.service_us",
                                         StatsRegistry::LatencyBucketsUs());
  WorkerPool pool(1);
  pool.SetStats(wait, service);
  // One slow task at the head of a 1-thread pool: the tasks behind it
  // must observe queue wait >= its service time.
  pool.Submit([] { usleep(30 * 1000); });
  for (int i = 0; i < 3; ++i) pool.Submit([] {});
  pool.Stop();  // drain-then-join
  CHECK_EQ(service->count(), 4);
  CHECK_EQ(wait->count(), 4);
  CHECK(service->sum() >= 30000);
  CHECK(wait->sum() >= 30000);  // the queued tasks sat behind the sleeper
}

static void TestDioWorkersPerPath() {
  // A positive value is the operator's pin: taken as it stands, whatever
  // the host and however many store paths.
  CHECK_EQ(DioWorkersPerPath(1, 64, 1), 1);
  CHECK_EQ(DioWorkersPerPath(2, 1, 4), 2);
  CHECK_EQ(DioWorkersPerPath(5, 128, 7), 5);
  // 0 derives: the node's total follows the cores...
  CHECK_EQ(DioWorkersPerPath(0, 8, 1), 8);
  CHECK_EQ(DioWorkersPerPath(0, 13, 1), 13);
  // ... between the floor (a one-core host, or a count the platform
  // does not know) and the cap ...
  CHECK_EQ(DioWorkersPerPath(0, 1, 1), kDioWorkersFloor);
  CHECK_EQ(DioWorkersPerPath(0, 0, 1), kDioWorkersFloor);
  CHECK_EQ(DioWorkersPerPath(0, 1024, 1), kDioWorkersCap);
  // ... and the store paths divide it (a pool a path: the node's total
  // is what is bounded), each keeping the floor.
  CHECK_EQ(DioWorkersPerPath(0, 16, 2), 8);
  CHECK_EQ(DioWorkersPerPath(0, 16, 3), 5);
  CHECK_EQ(DioWorkersPerPath(0, 4, 4), kDioWorkersFloor);
  CHECK_EQ(DioWorkersPerPath(0, 1024, 2), kDioWorkersCap / 2);
  CHECK_EQ(DioWorkersPerPath(0, 13, 0), 13);  // no path count yet: one
}

static void TestStatsRegistryPruneGauges() {
  StatsRegistry reg;
  reg.SetGauge("sync.peer.10.0.0.2:23000.lag_s", 4);
  reg.SetGauge("sync.peer.10.0.0.2:23000.connected", 1);
  reg.SetGauge("sync.peer.10.0.0.3:23000.lag_s", 9);
  reg.SetGauge("server.connections", 2);  // outside the prefix: untouched
  // Peer .3 left the group: prune everything under sync.peer. except
  // the surviving peer's family.
  int removed = reg.PruneGauges("sync.peer.",
                                {"sync.peer.10.0.0.2:23000."});
  CHECK_EQ(removed, 1);
  std::string json = reg.Json();
  CHECK(json.find("10.0.0.3") == std::string::npos);
  CHECK(json.find("sync.peer.10.0.0.2:23000.lag_s") != std::string::npos);
  CHECK(json.find("server.connections") != std::string::npos);
  // Re-appearing peer just re-registers (SetGauge is find-or-create).
  reg.SetGauge("sync.peer.10.0.0.3:23000.lag_s", 1);
  CHECK(reg.Json().find("10.0.0.3") != std::string::npos);
}


// -- lock-rank discipline (common/lockrank.h) ------------------------------

static void TestRankedMutex() {
  // Ascending-rank acquisition is legal and balances the held stack.
  RankedMutex outer(LockRank::kScrub);
  RankedMutex inner(LockRank::kLog);
  {
    std::lock_guard<RankedMutex> a(outer);
    std::lock_guard<RankedMutex> b(inner);
    if (kLockRankEnforced) CHECK_EQ(lockrank_detail::HeldCount(), 2);
  }
  if (kLockRankEnforced) CHECK_EQ(lockrank_detail::HeldCount(), 0);
  // try_lock participates in the held stack like lock().
  CHECK(outer.try_lock());  // NOLINT(lock-guard-discipline): testing the wrapper
  if (kLockRankEnforced) CHECK_EQ(lockrank_detail::HeldCount(), 1);
  outer.unlock();  // NOLINT(lock-guard-discipline)
  // Same-rank ASCENDING order keys: the RefAll stripe protocol.
  RankedMutex s2(LockRank::kChunkStripe, 2);
  RankedMutex s5(LockRank::kChunkStripe, 5);
  {
    std::unique_lock<RankedMutex> lk2(s2);
    std::unique_lock<RankedMutex> lk5(s5);
    // Out-of-order RELEASE is fine — only acquisition order is ranked.
    lk2.unlock();
  }
  if (kLockRankEnforced) CHECK_EQ(lockrank_detail::HeldCount(), 0);
  CHECK_EQ(std::string(LockRankName(LockRank::kChunkStripe)),
           "chunkstore.stripe");
}

static void TestRankedMutexThreaded() {
  // 4 threads hammer a correctly-ordered two-lock chain plus a ranked
  // spinlock; the TSan leg proves the checker's thread_local
  // bookkeeping (and the spinlock's acquire/release) is race-free, and
  // the counters prove mutual exclusion still holds through the wrapper.
  RankedMutex a(LockRank::kStatsRegistry);
  RankedMutex b(LockRank::kWorkers);
  RankedSpinLock s(LockRank::kTraceSlot);
  int both = 0;
  int spun = 0;
  std::vector<std::thread> threads;
  for (int t = 0; t < 4; ++t) {
    threads.emplace_back([&] {
      for (int i = 0; i < 1000; ++i) {
        std::lock_guard<RankedMutex> la(a);
        std::lock_guard<RankedMutex> lb(b);
        ++both;
        SpinGuard g(s);
        ++spun;
      }
    });
  }
  for (auto& th : threads) th.join();
  CHECK_EQ(both, 4 * 1000);
  CHECK_EQ(spun, 4 * 1000);
}

// Death-test driver: re-exec THIS binary with a violation flag (fork +
// exec keeps the child single-threaded at birth, which the sanitizer
// runtimes require), expect SIGABRT, and expect BOTH lock sites in the
// report.
static void ExpectChildAborts(const char* exe, const char* flag,
                              const char* expect_a, const char* expect_b) {
  int fds[2];
  CHECK_EQ(pipe(fds), 0);
  pid_t pid = fork();
  CHECK(pid >= 0);
  if (pid == 0) {
    dup2(fds[1], 2);
    close(fds[0]);
    close(fds[1]);
    execl(exe, exe, flag, static_cast<char*>(nullptr));
    _exit(127);
  }
  close(fds[1]);
  std::string err;
  char buf[4096];
  ssize_t r;
  while ((r = read(fds[0], buf, sizeof(buf))) > 0)
    err.append(buf, static_cast<size_t>(r));
  close(fds[0]);
  int st = 0;
  waitpid(pid, &st, 0);
  if (!(WIFSIGNALED(st) && WTERMSIG(st) == SIGABRT)) {
    std::fprintf(stderr, "FAIL %s: child (%s) did not SIGABRT; stderr:\n%s\n",
                 __FILE__, flag, err.c_str());
    ++g_failures;
    return;
  }
  CHECK(err.find(expect_a) != std::string::npos);
  CHECK(err.find(expect_b) != std::string::npos);
  CHECK(err.find("held by this thread") != std::string::npos);
}

static void TestRankedMutexInversionAborts(const char* exe) {
  if (!kLockRankEnforced) {
    std::printf("common_test: lockrank death tests SKIPPED "
                "(build without -DFDFS_LOCKRANK)\n");
    return;
  }
  // A thread acquiring a LOWER rank while holding a higher one must
  // abort, reporting the acquiring lock AND the held stack.
  ExpectChildAborts(exe, "--lockrank-inversion",
                    "chunkstore.stripe", "log.global");
  // The RefAll protocol specifically: same rank, DESCENDING stripe
  // keys must abort even though ascending is sanctioned.
  ExpectChildAborts(exe, "--lockrank-stripe-descend",
                    "ascending", "chunkstore.stripe");
  // Recursive acquisition of one instance is a deadlock in production;
  // the checker turns it into a deterministic abort.
  ExpectChildAborts(exe, "--lockrank-recursive",
                    "recursive", "sync.manager");
}

// Child-process violation bodies (reached only via the flags above).
static int RunLockRankViolation(const char* flag) {
  if (std::strcmp(flag, "--lockrank-inversion") == 0) {
    RankedMutex hi(LockRank::kLog);
    RankedMutex lo(LockRank::kChunkStripe);
    std::thread t([&] {
      std::lock_guard<RankedMutex> a(hi);
      std::lock_guard<RankedMutex> b(lo);  // rank 90 under rank 210: abort
    });
    t.join();
  } else if (std::strcmp(flag, "--lockrank-stripe-descend") == 0) {
    RankedMutex s5(LockRank::kChunkStripe, 5);
    RankedMutex s2(LockRank::kChunkStripe, 2);
    std::lock_guard<RankedMutex> a(s5);
    std::lock_guard<RankedMutex> b(s2);  // descending keys: abort
  } else if (std::strcmp(flag, "--lockrank-recursive") == 0) {
    RankedMutex m(LockRank::kSync);
    m.lock();  // NOLINT(lock-guard-discipline): deliberate violation
    // On a checked build the second lock aborts in PushOrDie BEFORE
    // touching the std::mutex; on an unchecked build it would be a
    // genuine self-deadlock, so only attempt it when enforced.
    if (kLockRankEnforced)
      m.lock();  // NOLINT(lock-guard-discipline): recursive; checker aborts
    m.unlock();  // NOLINT(lock-guard-discipline)
  } else {
    std::fprintf(stderr, "unknown flag %s\n", flag);
    return 2;
  }
  // Only reachable when FDFS_LOCKRANK is compiled out.
  std::printf("no abort\n");
  return 0;
}

// ---------------------------------------------------------------------------
// Metrics journal (common/metrog.h)
// ---------------------------------------------------------------------------

static StatsSnapshot MakeSnap(int64_t ops, int64_t errs, int64_t conns,
                              std::vector<int64_t> lat_counts) {
  StatsSnapshot s;
  s.counters["op.upload_file.count"] = ops;
  s.counters["op.upload_file.errors"] = errs;
  s.gauges["server.connections"] = conns;
  StatsSnapshot::Hist h;
  h.bounds = {100, 1000, 10000};
  h.counts = std::move(lat_counts);
  h.count = 0;
  for (int64_t c : h.counts) h.count += c;
  h.sum = h.count * 10;
  s.histograms["op.upload_file.latency_us"] = h;
  return s;
}

static void TestMetricsRecordCodec() {
  // Full -> delta -> delta chain with a new series, a tombstone, and
  // histogram growth; DecodeBuffer must reconstruct absolutes exactly.
  StatsSnapshot s1 = MakeSnap(10, 1, 3, {5, 2, 0, 0});
  s1.gauges["sync.peer.10.0.0.2:23000.lag_s"] = 7;
  StatsSnapshot s2 = MakeSnap(25, 1, 4, {5, 12, 3, 1});
  s2.counters["op.download_file.count"] = 9;  // appears mid-stream
  StatsSnapshot s3 = s2;                       // unchanged tick
  std::string buf = MetricsJournal::EncodeRecord(nullptr, s1, 111);
  buf += MetricsJournal::EncodeRecord(&s1, s2, 222);
  buf += MetricsJournal::EncodeRecord(&s2, s3, 333);
  size_t valid = 0;
  auto recs = MetricsJournal::DecodeBuffer(buf, &valid);
  CHECK_EQ(valid, buf.size());
  CHECK_EQ(recs.size(), 3u);
  CHECK_EQ(recs[0].first, 111);
  CHECK(recs[0].second.counters == s1.counters);
  CHECK(recs[0].second.gauges == s1.gauges);
  CHECK(recs[1].second.counters == s2.counters);
  // the pruned peer gauge died with the delta's tombstone
  CHECK_EQ(recs[1].second.gauges.count("sync.peer.10.0.0.2:23000.lag_s"), 0u);
  CHECK_EQ(recs[1].second.histograms["op.upload_file.latency_us"].count, 21);
  CHECK_EQ(recs[1].second.histograms["op.upload_file.latency_us"].counts[1],
           12);
  CHECK(recs[2].second.counters == s3.counters);

  // Torn tail: any truncation point inside the last frame drops exactly
  // that record and keeps the prefix.
  std::string torn = buf.substr(0, buf.size() - 3);
  auto recs2 = MetricsJournal::DecodeBuffer(torn, &valid);
  CHECK_EQ(recs2.size(), 2u);
  CHECK(valid < torn.size());
  // Corrupt one payload byte of the middle record: CRC rejects it and
  // the scan stops there (a delta chain cannot skip records).
  std::string flip = buf;
  size_t first_len = MetricsJournal::EncodeRecord(nullptr, s1, 111).size();
  flip[first_len + 20] ^= 0x5A;
  auto recs3 = MetricsJournal::DecodeBuffer(flip, &valid);
  CHECK_EQ(recs3.size(), 1u);

  // Retention cap: only the NEWEST max_records snapshots are kept, the
  // whole buffer still scans (valid covers every frame), and the
  // survivors are exact absolutes even though their delta bases were
  // dropped from the result.
  auto recs4 = MetricsJournal::DecodeBuffer(buf, &valid, 2);
  CHECK_EQ(valid, buf.size());
  CHECK_EQ(recs4.size(), 2u);
  CHECK_EQ(recs4[0].first, 222);
  CHECK_EQ(recs4[1].first, 333);
  CHECK(recs4[0].second.counters == s2.counters);
  CHECK(recs4[1].second.counters == s3.counters);
}

static void TestMetricsJournalDiskAndTornTail() {
  char tmpl[] = "/tmp/fdfs_metrog_XXXXXX";
  CHECK(mkdtemp(tmpl) != nullptr);
  std::string dir = tmpl;
  std::string err;
  {
    MetricsJournal j(dir, 1 << 20);
    CHECK(j.Open(&err));
    for (int i = 1; i <= 5; ++i)
      j.Append(1000 + i, MakeSnap(i * 10, i, i, {static_cast<int64_t>(i),
                                                 0, 0, 0}));
    CHECK_EQ(j.appended(), 5);
    auto recs = j.Decode(0);
    CHECK_EQ(recs.size(), 5u);
    CHECK_EQ(recs[4].second.counters["op.upload_file.count"], 50);
    // since-filter: only the records at/after the cut
    CHECK_EQ(j.Decode(1004).size(), 2u);
  }
  // kill -9 analogue: chop bytes off the journal tail, reopen, and the
  // intact prefix must survive while appends keep working.
  std::string path = dir + "/metrics.mj";
  struct stat st;
  CHECK_EQ(stat(path.c_str(), &st), 0);
  CHECK_EQ(truncate(path.c_str(), st.st_size - 5), 0);
  {
    MetricsJournal j(dir, 1 << 20);
    CHECK(j.Open(&err));
    CHECK(j.recovered_bytes() > 0);
    auto recs = j.Decode(0);
    CHECK_EQ(recs.size(), 4u);  // the torn record is gone, prefix intact
    CHECK_EQ(recs[3].second.counters["op.upload_file.count"], 40);
    // post-recovery appends start with a fresh full record
    j.Append(2000, MakeSnap(99, 9, 9, {1, 1, 1, 1}));
    auto recs2 = j.Decode(0);
    CHECK_EQ(recs2.size(), 5u);
    CHECK_EQ(recs2[4].second.counters["op.upload_file.count"], 99);
  }
  // Rotation: a tiny cap (clamped to 64 KB; rotate past 32 KB) with fat
  // records must rotate without losing decodability, and total retained
  // bytes must stay near the cap.
  {
    std::string dir2 = dir + "/rot";
    MetricsJournal j(dir2, 1);  // clamps to 64 KB
    CHECK(j.Open(&err));
    for (int tick = 0; tick < 6; ++tick) {
      StatsSnapshot s;
      for (int k = 0; k < 3000; ++k)
        s.gauges["g." + std::to_string(k)] = tick * 3000 + k;
      j.Append(5000 + tick, s);
    }
    auto recs = j.Decode(0);
    CHECK(!recs.empty());
    CHECK_EQ(recs.back().first, 5005);
    CHECK_EQ(recs.back().second.gauges.at("g.2999"), 5 * 3000 + 2999);
    CHECK(j.bytes_retained() <= (128 << 10));
  }
}

// ---------------------------------------------------------------------------
// SLO evaluator (common/sloeval.h)
// ---------------------------------------------------------------------------

static void TestSloReadings() {
  StatsSnapshot prev = MakeSnap(100, 0, 3, {10, 0, 0, 0});
  StatsSnapshot cur = MakeSnap(200, 10, 3, {10, 0, 99, 1});
  double v = 0;
  CHECK(SloEvaluator::ComputeReading("error_rate_pct", prev, cur, 1.0, &v));
  CHECK_EQ(static_cast<int64_t>(v), 10);  // 10 errors / 100 ops
  CHECK(SloEvaluator::ComputeReading("request_p99_ms", prev, cur, 1.0, &v));
  CHECK_EQ(static_cast<int64_t>(v * 1000), 10000);  // p99 bucket <=10000us
  // Overflow mass reads as 2x the last bound — still a breach signal.
  StatsSnapshot over = MakeSnap(300, 10, 3, {10, 0, 99, 50});
  CHECK(SloEvaluator::ComputeReading("request_p99_ms", cur, over, 1.0, &v));
  CHECK_EQ(static_cast<int64_t>(v * 1000), 20000);
  // No traffic in the window: the reading is unavailable, not zero.
  CHECK(!SloEvaluator::ComputeReading("error_rate_pct", cur, cur, 1.0, &v));
  // Gauge rules read current levels.
  cur.gauges["scrub.corrupt_unrepairable"] = 2;
  CHECK(SloEvaluator::ComputeReading("scrub_unrepairable", prev, cur, 1, &v));
  CHECK_EQ(static_cast<int64_t>(v), 2);
  CHECK(!SloEvaluator::ComputeReading("disk_fill_pct", prev, cur, 1, &v));
}

static void TestSloHysteresis() {
  EventLog log(32);
  SloEvaluator slo({{"error_rate_pct", 5.0, 2.5, true}}, &log);
  auto snap_at = [](int64_t ops, int64_t errs) {
    StatsSnapshot s;
    s.counters["op.x.count"] = ops;
    s.counters["op.x.errors"] = errs;
    return s;
  };
  StatsSnapshot a = snap_at(0, 0), b = snap_at(100, 50);
  slo.Tick(a, b, 1.0);  // reading 50% -> ewma 50 -> breach
  CHECK(slo.IsBreached("error_rate_pct"));
  CHECK_EQ(slo.breaches_active(), 1);
  CHECK_EQ(slo.breach_transitions(), 1);
  // One clean tick must NOT clear it (ewma 25 > clear 2.5): no flap.
  StatsSnapshot c = snap_at(200, 50);
  slo.Tick(b, c, 1.0);
  CHECK(slo.IsBreached("error_rate_pct"));
  // Sustained clean traffic decays the EWMA below clear -> recovered.
  StatsSnapshot last = c;
  for (int i = 0; i < 5; ++i) {
    StatsSnapshot next = last;
    next.counters["op.x.count"] += 100;
    slo.Tick(last, next, 1.0);
    last = next;
  }
  CHECK(!slo.IsBreached("error_rate_pct"));
  CHECK_EQ(slo.breaches_active(), 0);
  // Exactly one breach + one recovered event, in order.
  std::string dump = log.Json("storage", 1);
  CHECK(dump.find("slo.breach") != std::string::npos);
  CHECK(dump.find("slo.recovered") != std::string::npos);
  CHECK_EQ(log.recorded(), 2);
}

static void TestSloRuleOverrides() {
  IniConfig ini;
  std::string err;
  CHECK(ini.LoadString("error_rate_pct_threshold = 1.0\n"
                       "request_p99_ms_enabled = 0\n"
                       "disk_fill_pct_threshold = 70\n"
                       "disk_fill_pct_clear = 60\n",
                       &err));
  auto rules = SloEvaluator::LoadRules(ini);
  bool saw_err = false, saw_p99 = false, saw_disk = false;
  for (const SloRule& r : rules) {
    if (r.name == "error_rate_pct") {
      saw_err = true;
      CHECK_EQ(static_cast<int64_t>(r.threshold * 10), 10);
      // clear rescaled proportionally (default 5/2.5 -> 1/0.5)
      CHECK_EQ(static_cast<int64_t>(r.clear * 10), 5);
    }
    if (r.name == "request_p99_ms") {
      saw_p99 = true;
      CHECK(!r.enabled);
    }
    if (r.name == "disk_fill_pct") {
      saw_disk = true;
      CHECK_EQ(static_cast<int64_t>(r.threshold), 70);
      CHECK_EQ(static_cast<int64_t>(r.clear), 60);
    }
  }
  CHECK(saw_err && saw_p99 && saw_disk);
}

// ---------------------------------------------------------------------------
// Heat sketch (common/heatsketch.h)
// ---------------------------------------------------------------------------

static void TestHeatSketchExactWhenUnderCapacity() {
  // Below capacity the sketch IS exact: counts, bytes, per-op splits,
  // zero error bound.
  HeatSketch sketch(8, 1);
  for (int i = 0; i < 7; ++i) sketch.Touch("hot", HeatOp::kDownload, 10, false);
  sketch.Touch("hot", HeatOp::kUpload, 100, false);
  sketch.Touch("warm", HeatOp::kDownload, 5, false);
  sketch.Touch("warm", HeatOp::kDownload, 0, true);  // one error
  auto top = sketch.Top(2);
  CHECK_EQ(top.size(), 2u);
  CHECK_EQ(top[0].key, std::string("hot"));
  CHECK_EQ(top[0].hits, 8);
  CHECK_EQ(top[0].err_bound, 0);
  CHECK_EQ(top[0].bytes, 170);
  CHECK_EQ(top[0].op_count[0], 7);
  CHECK_EQ(top[0].op_count[1], 1);
  CHECK_EQ(top[1].key, std::string("warm"));
  CHECK_EQ(top[1].hits, 2);
  CHECK_EQ(top[1].err, 1);
  // JSON shape smoke (full decode parity lives in the codec golden)
  std::string js = sketch.TopJson("storage", 23000, 1);
  CHECK(js.find("\"entries\":[{\"key\":\"hot\"") != std::string::npos);
  CHECK(js.find("\"download\":{\"count\":7,\"bytes\":70}") !=
        std::string::npos);
}

static void TestHeatSketchAccuracy() {
  // Zipf-ish synthetic under real eviction pressure: a 64-key universe
  // against 16x4 tracked slots.  The space-saving theorems must hold:
  // hits is an overcount bounded by err_bound (hits >= true >=
  // hits - err_bound), the true hottest key ranks first, and the exact
  // top-5 surfaces in the sketch's top-5 (the acceptance bar the live
  // test applies to HEAT_TOP under load_cli --zipf).
  HeatSketch sketch(16, 4);
  std::vector<int64_t> truth(64);
  for (int i = 0; i < 64; ++i) truth[i] = 1000 / (i + 1);
  // interleave rounds so eviction pressure is realistic, not sorted
  for (int round = 0; round < 1000; ++round)
    for (int i = 0; i < 64; ++i)
      if (round < truth[i])
        sketch.Touch("group1/M00/k" + std::to_string(i), HeatOp::kDownload,
                     100, false);
  int64_t total = 0;
  for (int64_t t : truth) total += t;
  CHECK_EQ(sketch.touches(), total);
  auto top = sketch.Top(5);
  CHECK_EQ(top.size(), 5u);
  std::vector<std::string> top_keys;
  for (const auto& e : top) top_keys.push_back(e.key);
  for (int i = 0; i < 5; ++i) {
    // exact top-5 ⊆ sketch top-5 (both are 5 long, so sets match)
    std::string want = "group1/M00/k" + std::to_string(i);
    CHECK(std::find(top_keys.begin(), top_keys.end(), want) !=
          top_keys.end());
  }
  CHECK_EQ(top[0].key, std::string("group1/M00/k0"));
  for (const auto& e : top) {
    int idx = atoi(e.key.c_str() + strlen("group1/M00/k"));
    CHECK(e.hits >= truth[idx]);                  // never undercounts
    CHECK(e.hits - e.err_bound <= truth[idx]);    // honest error bound
  }
}

static void TestHeatSketchThreaded() {
  // TSan target: concurrent touchers on overlapping keys + a Top reader.
  HeatSketch sketch(32, 4);
  std::atomic<bool> stop{false};
  std::thread reader([&] {
    while (!stop.load()) (void)sketch.TopJson("storage", 1, 8);
  });
  std::vector<std::thread> writers;
  for (int t = 0; t < 4; ++t) {
    writers.emplace_back([&sketch, t] {
      for (int i = 0; i < 20000; ++i)
        sketch.Touch("k" + std::to_string((i * (t + 1)) % 97),
                     static_cast<HeatOp>(i % kHeatOpCount), i % 512,
                     i % 50 == 0);
    });
  }
  for (auto& w : writers) w.join();
  stop.store(true);
  reader.join();
  CHECK_EQ(sketch.touches(), 4 * 20000);
  auto top = sketch.Top(0);
  CHECK(!top.empty());
  int64_t hits = 0;
  for (const auto& e : top) hits += e.hits;
  CHECK(hits >= 4 * 20000 / 2);  // bounded undercount from evictions only
}

// -- thread ledger & profiler ---------------------------------------------

static void TestThreadRegistryBasics() {
  fdfs::ThreadRegistry& reg = fdfs::ThreadRegistry::Global();
  size_t before = reg.size();
  CHECK(std::string(fdfs::CurrentThreadName()).empty());
  {
    fdfs::ScopedThreadName ledger("test.main");
    CHECK(std::string(fdfs::CurrentThreadName()) == "test.main");
    CHECK(reg.size() == before + 1);
    // /proc read for our own tid must succeed and report sane ticks.
    int64_t ut = -1, st = -1;
    CHECK(fdfs::ReadThreadCpuTicks(fdfs::CurrentTid(), &ut, &st));
    CHECK(ut >= 0 && st >= 0);
  }
  CHECK(reg.size() == before);
  CHECK(std::string(fdfs::CurrentThreadName()).empty());
}

static void TestThreadRegistrySampleThreaded() {
  // Named threads burn CPU; SampleInto must publish each one's gauges
  // and prune them after the threads leave.
  fdfs::StatsRegistry stats;
  std::atomic<bool> stop{false};
  std::atomic<int> ready{0};
  auto burner = [&](const char* name) {
    fdfs::ScopedThreadName ledger(name);
    ready.fetch_add(1);
    volatile uint64_t sink = 0;
    while (!stop.load()) sink += sink * 31 + 7;
  };
  std::thread t1(burner, "unit.burn/0");
  std::thread t2(burner, "unit.burn/1");
  while (ready.load() < 2) std::this_thread::yield();
  fdfs::ThreadRegistry::Global().SampleInto(&stats);
  // Second sample after measurable CPU so cpu_pct has a delta window.
  std::this_thread::sleep_for(std::chrono::milliseconds(120));
  fdfs::ThreadRegistry::Global().SampleInto(&stats);
  fdfs::StatsSnapshot snap;
  stats.Snapshot(&snap);
  for (const char* name : {"unit.burn/0", "unit.burn/1"}) {
    std::string base = std::string("thread.") + name + ".";
    CHECK(snap.gauges.count(base + "cpu_pct") == 1);
    CHECK(snap.gauges.count(base + "utime_ms") == 1);
    CHECK(snap.gauges.count(base + "stime_ms") == 1);
    int64_t pct = snap.gauges[base + "cpu_pct"];
    CHECK(pct >= 0 && pct <= 100);
  }
  // A spinning thread over a 120ms window must show real CPU on at
  // least one of its rows (scheduler noise can zero one of them).
  CHECK(snap.gauges["thread.unit.burn/0.cpu_pct"] +
            snap.gauges["thread.unit.burn/1.cpu_pct"] >
        0);
  stop.store(true);
  t1.join();
  t2.join();
  fdfs::ThreadRegistry::Global().SampleInto(&stats);
  fdfs::StatsSnapshot after;
  stats.Snapshot(&after);
  for (const auto& [name, v] : after.gauges)
    CHECK(name.rfind("thread.unit.burn", 0) != 0);
}

static void TestProfilerGateAndCapture() {
  fdfs::Profiler& prof = fdfs::Profiler::Global();
  // Feature off (profile_max_hz = 0): refuse to arm, dump ENOTSUP.
  CHECK(prof.max_hz() == 0);
  CHECK(prof.Start(97, 1) == 95);
  CHECK(!prof.ever_started());
  std::string out;
  CHECK(prof.DumpJson("test", 0, &out) == 95);

  prof.set_max_hz(200);
  CHECK(prof.Start(0, 1) == 22);
  CHECK(prof.Start(97, 0) == 22);

  // Real capture: burn CPU under an armed window, then dump.
  CHECK(prof.Start(500, 2) == 0);  // asked above the cap:
  CHECK(prof.armed_hz() == 200);   // ...clamped to profile_max_hz
  CHECK(prof.active());
  volatile uint64_t sink = 0;
  int64_t until = fdfs::MonoUs() + 300 * 1000;
  while (fdfs::MonoUs() < until) sink += sink * 31 + 7;
  CHECK(prof.Stop() == 0);
  CHECK(!prof.active());
  int64_t got = prof.samples();
  CHECK(got > 0);  // 200 Hz over 300ms of pure spin: samples must land
  CHECK(prof.DumpJson("test", 123, &out) == 0);
  CHECK(out.find("\"role\":\"test\"") != std::string::npos);
  CHECK(out.find("\"port\":123") != std::string::npos);
  CHECK(out.find("\"stacks\":[") != std::string::npos);
  CHECK(out.find("\"active\":false") != std::string::npos);
  // Stop is idempotent; re-arm resets the window.
  CHECK(prof.Stop() == 0);
  CHECK(prof.Start(100, 1) == 0);
  CHECK(prof.samples() <= got);  // counters reset on re-arm
  CHECK(prof.Stop() == 0);
}

static void TestProfilerCtlHammerAgainstLiveThreads() {
  // Signal-safety hammer: spinning threads receive SIGPROF while the
  // control path arms/disarms/dumps concurrently.  The assertion is
  // survival (no deadlock, no crash, no torn slab) — TSan and the
  // lock-rank checker judge the rest.
  fdfs::Profiler& prof = fdfs::Profiler::Global();
  prof.set_max_hz(500);
  std::atomic<bool> stop{false};
  std::vector<std::thread> burners;
  for (int i = 0; i < 3; ++i)
    burners.emplace_back([&stop, i] {
      fdfs::ScopedThreadName ledger("hammer.burn/" + std::to_string(i));
      volatile uint64_t sink = 0;
      while (!stop.load()) sink += sink * 131 + 17;
    });
  for (int round = 0; round < 25; ++round) {
    CHECK(prof.Start(500, 2) == 0);
    std::this_thread::sleep_for(std::chrono::milliseconds(2));
    if (round % 3 == 0) {
      std::string out;
      CHECK(prof.DumpJson("test", 0, &out) == 0);
      CHECK(!out.empty() && out.front() == '{' && out.back() == '}');
    }
    if (round % 2 == 0) CHECK(prof.Stop() == 0);
  }
  CHECK(prof.Stop() == 0);
  stop.store(true);
  for (auto& t : burners) t.join();
  // Leave the singleton disarmed-but-gated-off for any later test.
  prof.set_max_hz(0);
}

// -- gray-failure health layer (common/healthmon.h) ------------------------

static void TestHealthMonitorScoresAndTrailer() {
  HealthMonitor& hm = HealthMonitor::Global();
  hm.Reset();
  // No peers and no self signal yet: the beat stays trailerless (old
  // trackers see exactly the pre-health wire).
  CHECK(hm.PackBeatTrailer().empty());

  // Self score: each stalled thread costs 50; a probe past the
  // threshold costs 50, past 4x costs 75; clamped to [0, 100].
  hm.SetStalledThreads(0);
  hm.SetProbe(1500, 2500, 1000);  // 2.5ms probes, 1s threshold: clean
  CHECK_EQ(hm.SelfScore(), 100);
  hm.SetProbe(1500, 2500000, 1000);  // 2.5s write probe: gray disk
  CHECK_EQ(hm.SelfScore(), 50);
  hm.SetProbe(1500, 4100000, 1000);  // > 4x threshold: hard-degraded
  CHECK_EQ(hm.SelfScore(), 25);
  hm.SetStalledThreads(2);
  CHECK_EQ(hm.SelfScore(), 0);  // 100 - 100 - 75, clamped

  // The codec-golden fixture (tools/codec_cli.cc health-status and
  // tests/test_health.py assert the same arithmetic).
  hm.SetStalledThreads(1);
  hm.SetProbe(1500, 2500, 1000);
  for (int i = 0; i < 3; ++i)
    hm.Feed("10.0.0.2:23000", "fetch", true, 50000, 1000);
  hm.Feed("10.0.0.2:23000", "fetch", false, 950000, 1000);  // timeout-shaped
  hm.Feed("10.0.0.2:23000", "beat", true, 2000, 2000);
  hm.Feed("10.0.0.2:23000", "beat", true, 2000, 2000);
  hm.Feed("10.0.0.9:23001", "probe", false, 100, 2000);  // fast hard fail
  // fetch: 100 - round(.2*60) - round(.2*40) - 50ms latency penalty = 75;
  // beat stays 100; the composite per peer is the MIN across op classes.
  CHECK_EQ(hm.PeerScore("10.0.0.2:23000"), 75);
  CHECK_EQ(hm.PeerScore("10.0.0.9:23001"), 88);  // errors only, no latency
  CHECK_EQ(hm.PeerScore("1.2.3.4:1"), -1);       // never seen

  auto rows = hm.Snapshot();
  CHECK_EQ(rows.size(), 3u);  // (addr, op)-sorted
  CHECK(rows[0].addr == "10.0.0.2:23000" && rows[0].op == "beat");
  CHECK_EQ(rows[0].score, 100);
  CHECK_EQ(rows[0].ops, 2);
  CHECK(rows[1].op == "fetch");
  CHECK_EQ(rows[1].score, 75);
  CHECK_EQ(rows[1].rpc_ewma_us, 50000);  // failures never move latency
  CHECK_EQ(rows[1].error_pct, 20);
  CHECK_EQ(rows[1].timeout_pct, 20);
  CHECK(rows[1].ops == 4 && rows[1].errors == 1 && rows[1].timeouts == 1);
  CHECK(rows[2].addr == "10.0.0.9:23001" && rows[2].op == "probe");
  CHECK_EQ(rows[2].score, 88);
  CHECK(rows[2].errors == 1 && rows[2].timeouts == 0);

  // Beat-trailer roundtrip: 1B version + 8B self + 8B n + n x 32B.
  std::string t = hm.PackBeatTrailer();
  CHECK_EQ(t.size(), static_cast<size_t>(17 + 2 * 32));
  BeatHealthTrailer ht;
  CHECK(ParseBeatHealthTrailer(t.data(), t.size(), &ht));
  CHECK_EQ(ht.self_score, 50);
  CHECK_EQ(ht.peers.size(), 2u);
  CHECK(ht.peers[0].first == "10.0.0.2:23000" && ht.peers[0].second == 75);
  CHECK(ht.peers[1].first == "10.0.0.9:23001" && ht.peers[1].second == 88);
  std::string bad = t;
  bad[0] = 9;  // unknown version: refuse, don't guess
  CHECK(!ParseBeatHealthTrailer(bad.data(), bad.size(), &ht));
  CHECK(!ParseBeatHealthTrailer(t.data(), 16, &ht));          // short header
  CHECK(!ParseBeatHealthTrailer(t.data(), t.size() - 1, &ht));  // torn entry

  // Gauges publish per ADDR (min score across ops) and prune on Reset.
  StatsRegistry reg;
  hm.PublishGauges(&reg);
  std::string json = reg.Json();
  CHECK(json.find("\"peer.10.0.0.2:23000.score\":75") != std::string::npos);
  CHECK(json.find("\"peer.10.0.0.9:23001.score\":88") != std::string::npos);
  CHECK(json.find("\"health.score\":50") != std::string::npos);
  hm.Reset();
  hm.PublishGauges(&reg);
  CHECK(reg.Json().find("peer.10.0.0.2") == std::string::npos);

  // Op-class bucketing: the opcode -> class mapping is part of the
  // cross-language contract (mirrored in the health-status golden).
  CHECK(std::string(HealthMonitor::OpClassFor(111)) == "probe");
  CHECK(std::string(HealthMonitor::OpClassFor(83)) == "beat");
  CHECK(std::string(HealthMonitor::OpClassFor(129)) == "fetch");
  CHECK(std::string(HealthMonitor::OpClassFor(145)) == "ec");
  CHECK(std::string(HealthMonitor::OpClassFor(16)) == "sync");
  CHECK(std::string(HealthMonitor::OpClassFor(11)) == "rpc");

  CHECK(hm.PackBeatTrailer().empty());  // Reset cleared the self signal
}

static void TestThreadRegistryWatchdog() {
  ThreadRegistry& tr = ThreadRegistry::Global();
  std::atomic<bool> stop{false};
  std::atomic<bool> do_beat{false};
  std::thread victim([&] {
    ScopedThreadName ledger("watchdog.victim");
    BeatThreadHeartbeat();
    while (!stop.load()) {
      if (do_beat.exchange(false)) BeatThreadHeartbeat();
      usleep(2000);
    }
  });
  // A never-beating thread has NO heartbeat contract: the watchdog must
  // not enroll it (false-positive-free by construction).
  std::atomic<bool> stop_quiet{false};
  std::thread quiet([&] {
    ScopedThreadName ledger("watchdog.quiet");
    while (!stop_quiet.load()) usleep(2000);
  });
  usleep(60 * 1000);  // victim's last beat is now ~60ms old
  ThreadRegistry::WatchdogResult wd = tr.WatchdogScan(30 * 1000);
  bool victim_stalled = false, victim_newly = false, quiet_stalled = false;
  for (const ThreadRegistry::Stall& s : wd.stalled) {
    if (s.name == "watchdog.victim") {
      victim_stalled = true;
      victim_newly = s.newly;
      CHECK(s.age_us >= 30 * 1000);
    }
    if (s.name == "watchdog.quiet") quiet_stalled = true;
  }
  CHECK(victim_stalled && victim_newly);
  CHECK(!quiet_stalled);
  // Second scan: still stalled, but no longer NEW (one event per outage).
  wd = tr.WatchdogScan(30 * 1000);
  victim_newly = true;
  for (const ThreadRegistry::Stall& s : wd.stalled)
    if (s.name == "watchdog.victim") victim_newly = s.newly;
  CHECK(!victim_newly);
  // The thread beats again: the outage ends and is reported ONCE.
  do_beat.store(true);
  for (int i = 0; i < 100 && do_beat.load(); ++i) usleep(2000);
  wd = tr.WatchdogScan(30 * 1000);
  bool recovered = false;
  for (const std::string& n : wd.recovered)
    if (n == "watchdog.victim") recovered = true;
  CHECK(recovered);
  for (const ThreadRegistry::Stall& s : wd.stalled)
    CHECK(s.name != "watchdog.victim");
  // Heartbeats(): the DumpState ledger view — victim has an age, the
  // never-beating thread reads -1.
  bool saw_victim = false, saw_quiet = false;
  for (const ThreadRegistry::HeartbeatEntry& h : tr.Heartbeats()) {
    if (h.name == "watchdog.victim") {
      saw_victim = true;
      CHECK(h.age_us >= 0);
    }
    if (h.name == "watchdog.quiet") {
      saw_quiet = true;
      CHECK_EQ(h.age_us, -1);
    }
  }
  CHECK(saw_victim && saw_quiet);
  stop.store(true);
  stop_quiet.store(true);
  victim.join();
  quiet.join();
}

// -- per-request stage intervals (StageTrace) -----------------------------

static void TestStageTraceNestingAndSums() {
  StageTrace t;
  t.Reset();
  t.Add(Stage::kRecv, 100, 150);
  {
    StageScope fp(&t, Stage::kFingerprint);
    {
      StageScope cdc(&t, Stage::kCdc);
    }
    StageScope rpc(&t, Stage::kFpRpc);
    rpc.SetArgs(7, 4096);
    rpc.End();
    rpc.End();  // idempotent
  }
  StageScope cs(&t, Stage::kCsWrite);
  cs.End();
  CHECK_EQ(t.n, 5);
  CHECK(!t.truncated);
  CHECK_EQ(t.open, -1);
  CHECK(t.iv[0].stage == Stage::kRecv && t.iv[0].parent == -1);
  CHECK_EQ(t.Sum(Stage::kRecv), 50);
  CHECK(t.iv[1].stage == Stage::kFingerprint && t.iv[1].parent == -1);
  CHECK(t.iv[2].stage == Stage::kCdc && t.iv[2].parent == 1);
  CHECK(t.iv[3].stage == Stage::kFpRpc && t.iv[3].parent == 1);
  CHECK(t.iv[3].args[0] == 7 && t.iv[3].args[1] == 4096);
  CHECK(t.iv[4].stage == Stage::kCsWrite && t.iv[4].parent == -1);
  // children inside their parent, siblings in order
  CHECK(t.iv[1].start_us <= t.iv[2].start_us);
  CHECK(t.iv[2].end_us <= t.iv[3].start_us);
  CHECK(t.iv[3].end_us <= t.iv[1].end_us);
  CHECK(t.iv[1].end_us <= t.iv[4].start_us);
  // every sum is the sum of its stage's intervals
  for (int s = 0; s < static_cast<int>(Stage::kCount); ++s) {
    int64_t sum = 0;
    for (int i = 0; i < t.n; ++i)
      if (static_cast<int>(t.iv[i].stage) == s)
        sum += t.iv[i].end_us - t.iv[i].start_us;
    CHECK_EQ(t.sum_us[s], sum);
  }
  t.Reset();
  CHECK_EQ(t.n, 0);
  CHECK_EQ(t.Sum(Stage::kRecv), 0);
}

static void TestStageTraceOverflowKeepsSums() {
  StageTrace t;
  t.Reset();
  StageScope outer(&t, Stage::kFingerprint);
  for (int i = 0; i < StageTrace::kCapacity + 10; ++i)
    t.Add(Stage::kCsWrite, 1000 * i, 1000 * i + 7);
  {
    StageScope late(&t, Stage::kBinlog);  // does not fit
    struct timespec ts = {0, 2000000};
    nanosleep(&ts, nullptr);
  }
  outer.End();
  CHECK_EQ(t.n, StageTrace::kCapacity);
  CHECK(t.truncated);
  CHECK_EQ(t.Sum(Stage::kCsWrite), 7 * (StageTrace::kCapacity + 10));
  CHECK(t.Sum(Stage::kBinlog) >= 2000);
  CHECK_EQ(t.open, -1);  // the one that fit closed; the late one never opened
  CHECK(t.iv[0].end_us - t.iv[0].start_us >= 2000);
  std::string line = StageLineJson(t, 11, 0, t.iv[0].start_us, 5, 9);
  CHECK(line.find("\"truncated\":1") != std::string::npos);
}

static void TestStageTraceNullAndThreadLocal() {
  {
    StageScope nothing(nullptr, Stage::kCdc);  // no recorder: a no-op
    nothing.SetArgs(3, 4);
  }
  CHECK(CurrentStageTrace() == nullptr);
  StageTrace t;
  t.Reset();
  {
    StageTraceBinding bind(&t);
    CHECK(CurrentStageTrace() == &t);
    std::thread other([] { CHECK(CurrentStageTrace() == nullptr); });
    other.join();
    {
      StageTraceBinding inner(nullptr);
      StageScope hidden(CurrentStageTrace(), Stage::kCdc);
    }
    StageScope seen(CurrentStageTrace(), Stage::kCdc);
  }
  CHECK(CurrentStageTrace() == nullptr);
  CHECK_EQ(t.n, 1);
}

static void TestStageLineFormat() {
  StageTrace t;
  t.Reset();
  t.Add(Stage::kRecv, 1000, 1812);
  t.Add(Stage::kDioWait, 1812, 1815);
  t.iv[t.n++] =
      StageTrace::Interval{1815, 2815, {0, 0}, Stage::kFingerprint, -1};
  t.iv[t.n++] = StageTrace::Interval{1900, 2800, {-5, 65536}, Stage::kFpRpc, 2};
  std::string line = StageLineJson(t, 11, 0, 1000, 1700000000000000LL, 2000);
  CHECK(line ==
        "{\"event\":\"stages\",\"cmd\":11,\"status\":0,\"t0_mono_us\":1000,"
        "\"t0_wall_us\":1700000000000000,\"dur_us\":2000,\"truncated\":0,"
        "\"spans\":[[\"storage.recv\",0,812,-1],[\"dio.queue_wait\",812,3,-1],"
        "[\"storage.fingerprint\",815,1000,-1],"
        "[\"storage.fp_rpc\",900,900,2,{\"session\":-5,"
        "\"base_offset\":65536}]]}");
  CHECK(line.find(' ') == std::string::npos);  // one token to column parsers
  CHECK(line.find('\n') == std::string::npos);
}

// `common_test --stage-cost`: what one interval costs with no sink on
// (OPERATIONS.md, "Tracing", the cost paragraph).
static int RunStageCost() {
  StageTrace t;
  const int kRounds = 200000, kPer = 10;
  int64_t t0 = MonoUs();
  for (int r = 0; r < kRounds; ++r) {
    t.Reset();
    for (int i = 0; i < kPer; ++i) {
      StageScope s(&t, Stage::kCsWrite);
    }
  }
  int64_t with = MonoUs() - t0;
  t0 = MonoUs();
  for (int r = 0; r < kRounds; ++r) {
    for (int i = 0; i < kPer; ++i) {
      StageScope s(nullptr, Stage::kCsWrite);
    }
  }
  int64_t without = MonoUs() - t0;
  std::printf("{\"ns_per_interval\":%.1f,\"ns_per_null_guard\":%.2f,"
              "\"intervals\":%d,\"recorded\":%d}\n",
              with * 1000.0 / (kRounds * kPer),
              without * 1000.0 / (kRounds * kPer), kRounds * kPer, t.n);
  return 0;
}

int main(int argc, char** argv) {
  if (argc > 1 && std::strncmp(argv[1], "--lockrank-", 11) == 0)
    return RunLockRankViolation(argv[1]);
  if (argc > 1 && std::strcmp(argv[1], "--stage-cost") == 0)
    return RunStageCost();

  TestEndian();
  TestBase64();
  TestCrc32();
  TestSha1();
  TestFileId();
  TestLocalPath();
  TestIni();
  TestProtocolConstants();
  TestStatsRegistry();
  TestTraceCtxWire();
  TestTraceRing();
  TestTraceRingThreaded();
  TestTraceCorrelator();
  TestStageTraceNestingAndSums();
  TestStageTraceOverflowKeepsSums();
  TestStageTraceNullAndThreadLocal();
  TestStageLineFormat();
  TestEventLog();
  TestEventLogThreaded();
  TestEventLoopLagHook();
  TestWorkerPoolQueueStats();
  TestDioWorkersPerPath();
  TestStatsRegistryPruneGauges();
  TestRankedMutex();
  TestRankedMutexThreaded();
  TestRankedMutexInversionAborts(argv[0]);
  TestMetricsRecordCodec();
  TestMetricsJournalDiskAndTornTail();
  TestSloReadings();
  TestSloHysteresis();
  TestSloRuleOverrides();
  TestHeatSketchExactWhenUnderCapacity();
  TestHeatSketchAccuracy();
  TestHeatSketchThreaded();
  TestThreadRegistryBasics();
  TestThreadRegistrySampleThreaded();
  TestProfilerGateAndCapture();
  TestProfilerCtlHammerAgainstLiveThreads();
  TestHealthMonitorScoresAndTrailer();
  TestThreadRegistryWatchdog();
  if (g_failures == 0) {
    std::printf("common_test: ALL PASS\n");
    return 0;
  }
  std::printf("common_test: %d FAILURES\n", g_failures);
  return 1;
}
