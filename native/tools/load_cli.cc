// Native load-generation harness (reference: the `test/` directory —
// test_upload.c / test_download.c / test_delete.c drive a live cluster
// from N processes and write per-op `.result` records; combine_result.c
// merges them into QPS + latency).  The rebuild's equivalent is one
// binary with subcommands; concurrency is threads (each with its own
// connections) and multiple processes compose the same way — `combine`
// merges any number of result files.
//
// Result record format (one line per op):
//   <start_us> <latency_us> <status> <bytes> <class> <file_id>
// where <class> is the priority class the op was tagged with on the
// wire (0..4) or 255 for untagged (the daemon applies its opcode
// default).  `combine` also accepts the older five-field format
// (records before the class column existed count as untagged).
//
// Usage:
//   fdfs_load upload   <tracker ip:port> <n_ops> <size> <threads> <result>
//                      [unique_payloads]   (0/absent = every op unique)
//   fdfs_load upload   <tracker ip:port> --small-files N --file-bytes B
//                      <threads> <result>
//                      (small-file corpus mode, ISSUE 9: N unique files
//                      of B bytes each — the ingest arm of the slab-
//                      packing bench, equivalent to n_ops=N size=B with
//                      every payload unique)
//   fdfs_load download <tracker ip:port> <ids_file> <n_ops> <threads> <result>
//                      [--zipf <s> [--zipf-keys N] [--zipf-seed S]]
//                      [--hot-keys K:pct]
//   fdfs_load delete   <tracker ip:port> <ids_file> <threads> <result>
//   fdfs_load combine  <result files...>     (prints one JSON line)
//   fdfs_load zipf-sample <s> <keys> <n> [seed]   (prints n key indices,
//                      one per line — the sampler the download mode
//                      uses, exposed for deterministic unit tests)
//
// `upload` also appends the minted file ids to <result>.ids for the
// download/delete phases.
//
// --open-loop --rate R (upload and download, any position after the
// mode): open-loop arrival mode (ISSUE 11's cluster load harness).
// Op i is SCHEDULED at t0 + i/R seconds across ALL threads combined,
// and its latency clock starts at the scheduled instant, not when a
// worker got around to it — so when the cluster falls behind the
// offered rate, the backlog lands in the latency percentiles instead
// of silently throttling the load (the closed-loop coordinated-
// omission failure).  Threads (<threads> = the concurrency cap) only
// bound how many ops may be in flight at once.
//
// --priority P (upload/download/delete, any position after the mode):
// tag every storage op with priority class P (0 control .. 4
// background) via the 1-byte PRIORITY prefix frame, so the admission
// ladder sheds by the declared class instead of the opcode default.
// --priority-mix <spec> instead assigns classes probabilistically:
// spec is comma-separated `[label:]class:weight` entries (e.g.
// `read:2:0.7,write:3:0.3` — labels are documentation only); op i is
// hashed deterministically onto the weight distribution, so a run's
// class assignment is reproducible regardless of thread interleaving
// (the zipf-picker discipline).  `combine` reports per-class op
// counts, admitted/shed splits (shed = EBUSY 16), and latency
// percentiles under "by_class".
//
// --conns N (upload/download/delete, any position after the mode):
// shared storage-connection budget across ALL worker threads.  Workers
// check a connection out of a pool per op; when every slot is busy the
// worker blocks until one is returned, so `--conns 1` serializes all
// storage traffic through one socket (the pre-multiplexing client
// shape) while `--conns >= threads` restores full parallelism — the
// knob that makes client-side multiplexing wins measurable from the
// harness side.  0/absent = unlimited (one conn per worker, the old
// behaviour).  Every run prints a `{"conns_budget": ...}` JSON line to
// stdout with the EFFECTIVE counts (opened/peak/waits) so the bench
// harness can verify the topology it asked for is the one it got.
//
// --zipf <s>: key-popularity mode for downloads (ISSUE 8 / ROADMAP
// item 2's load harness seed).  Instead of round-robin over the ids
// file, op i fetches the id Zipf(s) picks over a bounded key universe
// (--zipf-keys, default min(1000, #ids); rank 1 = the FIRST id in the
// file, weight 1/rank^s).  Sampling is keyed on the op index with a
// fixed seed (--zipf-seed, default 42), so a run is DETERMINISTIC
// regardless of thread count or interleaving — the heat-sketch
// acceptance test replays the exact same skew every time.
//
// --hot-keys K:pct (download; ISSUE 20's elastic-replication bench
// mode): the FIRST K ids in the file form a hot set that receives
// pct% of the ops (uniform within the set); the rest spread uniformly
// over the remaining ids.  Unlike --zipf's smooth rank curve this
// pins an exact hot-set size and traffic share, so a promotion
// threshold can be aimed at precisely K keys.  Mutually exclusive
// with --zipf.  Each record's trailing token marks its key class
// ("hot"/"cold"), and `combine` reports per-key-class op counts and
// latency percentiles under "by_key_class" — the number the bench
// compares across the promotion-on/off arms.  Deterministic on the op
// index (the zipf-picker discipline).
#include <stdio.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <fstream>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "common/bytes.h"
#include "common/net.h"
#include "common/protocol_gen.h"

using namespace fdfs;

namespace {

constexpr int kTimeoutMs = 60000;

int64_t MonoUs() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000 + ts.tv_nsec / 1000;
}

constexpr int kUntagged = 255;

struct OpRecord {
  int64_t start_us;
  int64_t latency_us;
  int status;  // 0 ok, errno-style otherwise; -1 = transport failure
  int64_t bytes;
  int cls;     // wire priority class, kUntagged when no frame was sent
  std::string file_id;
  // "hot"/"cold" under --hot-keys, "" otherwise (a trailing record
  // token; absent = unclassed, the append-only record discipline).
  std::string key_class;
};

// One request/response on a blocking fd.  Returns false on transport
// failure; *status carries the server's header status byte.
bool Rpc(int fd, uint8_t cmd, const std::string& body, std::string* resp,
         uint8_t* status) {
  return NetRpc(fd, cmd, body, resp, status, 1LL << 31, kTimeoutMs);
}

std::string PackGroup(const std::string& group) {
  std::string out(16, '\0');
  memcpy(out.data(), group.data(), std::min<size_t>(group.size(), 16));
  return out;
}

bool SplitAddr(const std::string& addr, std::string* host, int* port) {
  size_t c = addr.rfind(':');
  if (c == std::string::npos) return false;
  *host = addr.substr(0, c);
  *port = atoi(addr.c_str() + c + 1);
  return *port > 0;
}

bool SplitId(const std::string& file_id, std::string* group,
             std::string* remote) {
  size_t s = file_id.find('/');
  if (s == std::string::npos) return false;
  *group = file_id.substr(0, s);
  *remote = file_id.substr(s + 1);
  return true;
}

// A pooled connection to one peer; reconnects lazily after failures (the
// reference load clients keep one connection per process the same way).
class Peer {
 public:
  Peer(std::string host, int port) : host_(std::move(host)), port_(port) {}
  ~Peer() { Close(); }
  bool Call(uint8_t cmd, const std::string& body, std::string* resp,
            uint8_t* status, int cls = kUntagged) {
    for (int attempt = 0; attempt < 2; ++attempt) {
      if (fd_ < 0) {
        std::string err;
        fd_ = TcpConnect(host_, port_, kTimeoutMs, &err);
        if (fd_ < 0) return false;
      }
      if (cls != kUntagged) {
        // PRIORITY prefix frame (no response of its own): 10B header
        // with pkg_len=1 + the class byte, tagging the next request.
        uint8_t frame[kHeaderSize + 1] = {0};
        PutInt64BE(kPriorityFrameLen, frame);
        frame[8] = static_cast<uint8_t>(StorageCmd::kPriority);
        frame[kHeaderSize] = static_cast<uint8_t>(cls);
        if (!SendAll(fd_, frame, sizeof(frame), kTimeoutMs)) {
          Close();
          continue;
        }
      }
      if (Rpc(fd_, cmd, body, resp, status)) return true;
      Close();  // stale/broken connection: one reconnect attempt
    }
    return false;
  }
  void Close() {
    if (fd_ >= 0) {
      close(fd_);
      fd_ = -1;
    }
  }
  const std::string& host() const { return host_; }
  int port() const { return port_; }

 private:
  std::string host_;
  int port_;
  int fd_ = -1;
};

// Shared storage-connection pool (--conns N).  All workers draw their
// storage connections from here; `budget` caps the LIVE connection
// count across every endpoint, and a worker whose op finds the budget
// exhausted blocks until someone returns one.  Idle conns are parked
// per endpoint and reused LIFO (warmest socket first); when the cap is
// tight and the op targets an endpoint with no idle conn, an idle conn
// to a DIFFERENT endpoint is retired to free budget instead of
// deadlocking on endpoint churn.  budget <= 0 = unlimited, which
// degenerates to the old one-conn-per-worker shape (each worker gets
// back the conn it just returned).
class StoragePool {
 public:
  ~StoragePool() {
    for (Peer* p : all_) delete p;
  }
  // Must be called before workers start; not thread-safe.
  void set_budget(int budget) { budget_ = budget; }
  int budget() const { return budget_; }

  Peer* Checkout(const std::string& host, int port) {
    std::unique_lock<RankedMutex> lk(mu_);
    const std::string key = host + ":" + std::to_string(port);
    for (;;) {
      auto it = idle_.find(key);
      if (it != idle_.end() && !it->second.empty()) {
        Peer* p = it->second.back();
        it->second.pop_back();
        return p;
      }
      if (budget_ <= 0 || live_ < budget_) {
        ++live_;
        ++opened_;
        peak_ = std::max(peak_, live_);
        Peer* p = new Peer(host, port);
        all_.push_back(p);
        return p;
      }
      // Cap reached, nothing idle for THIS endpoint: retire an idle
      // conn to another endpoint if one exists, else wait for a return.
      bool retired = false;
      for (auto& [k, v] : idle_) {
        (void)k;
        if (!v.empty()) {
          v.back()->Close();  // freed via all_ at exit
          v.pop_back();
          --live_;
          retired = true;
          break;
        }
      }
      if (retired) continue;
      ++waits_;
      cv_.wait(lk);
    }
  }

  void Return(Peer* p) {
    std::lock_guard<RankedMutex> lk(mu_);
    idle_[p->host() + ":" + std::to_string(p->port())].push_back(p);
    cv_.notify_one();
  }

  // Effective-count report for the harness; call after workers join.
  void PrintStats() const {
    printf(
        "{\"conns_budget\": %d, \"conns_opened\": %lld, "
        "\"conns_peak\": %d, \"conn_waits\": %lld}\n",
        budget_, static_cast<long long>(opened_), peak_,
        static_cast<long long>(waits_));
  }

 private:
  mutable RankedMutex mu_{LockRank::kToolOutput};
  std::condition_variable_any cv_;
  int budget_ = 0;
  int live_ = 0;     // created minus retired (checked out or idle)
  int peak_ = 0;     // max live_ ever
  int64_t opened_ = 0;  // total connections ever created
  int64_t waits_ = 0;   // checkouts that had to block on the cap
  std::map<std::string, std::vector<Peer*>> idle_;
  std::vector<Peer*> all_;  // owns every Peer ever created
};

// RAII checkout so early-exit paths in the workers cannot leak a
// pooled connection (which under --conns 1 would wedge every worker).
class PooledPeer {
 public:
  PooledPeer(StoragePool* pool, const std::string& host, int port)
      : pool_(pool), peer_(pool->Checkout(host, port)) {}
  ~PooledPeer() { pool_->Return(peer_); }
  PooledPeer(const PooledPeer&) = delete;
  PooledPeer& operator=(const PooledPeer&) = delete;
  Peer* operator->() { return peer_; }

 private:
  StoragePool* pool_;
  Peer* peer_;
};

// tracker query_store (cmd 101): resp = 16B group + 16B ip + 8B port +
// 1B store-path index.
bool QueryStore(Peer* tracker, std::string* group, std::string* ip,
                int* port, uint8_t* spi) {
  std::string resp;
  uint8_t status = 0;
  if (!tracker->Call(
          static_cast<uint8_t>(TrackerCmd::kServiceQueryStoreWithoutGroupOne),
          "", &resp, &status) ||
      status != 0 || resp.size() < 41)
    return false;
  *group = std::string(resp.c_str(), strnlen(resp.c_str(), 16));
  *ip = std::string(resp.data() + 16, strnlen(resp.data() + 16, 16));
  *port = static_cast<int>(
      GetInt64BE(reinterpret_cast<const uint8_t*>(resp.data()) + 32));
  *spi = static_cast<uint8_t>(resp[40]);
  return true;
}

// tracker query_fetch/update (cmd 102/103): resp = 16B ip + 8B port.
bool QueryFetch(Peer* tracker, uint8_t cmd, const std::string& file_id,
                std::string* ip, int* port) {
  std::string group, remote;
  if (!SplitId(file_id, &group, &remote)) return false;
  std::string resp;
  uint8_t status = 0;
  if (!tracker->Call(cmd, PackGroup(group) + remote, &resp, &status) ||
      status != 0 || resp.size() < 24)
    return false;
  *ip = std::string(resp.data(), strnlen(resp.data(), 16));
  *port = static_cast<int>(
      GetInt64BE(reinterpret_cast<const uint8_t*>(resp.data()) + 16));
  return true;
}

// Zipf(s) sampler over key ranks [0, n): rank r carries weight
// 1/(r+1)^s.  Pick(i) hashes the op index through splitmix64 with a
// fixed seed, so the i-th operation of a run always fetches the same
// key — deterministic skew independent of thread scheduling.
class ZipfPicker {
 public:
  ZipfPicker(double s, size_t n, uint64_t seed) : seed_(seed) {
    cdf_.resize(n == 0 ? 1 : n);
    double acc = 0;
    for (size_t r = 0; r < cdf_.size(); ++r) {
      acc += 1.0 / std::pow(static_cast<double>(r + 1), s);
      cdf_[r] = acc;
    }
    total_ = acc;
  }
  size_t Pick(int64_t i) const {
    uint64_t x = seed_ + 0x9E3779B97F4A7C15ULL * (static_cast<uint64_t>(i) + 1);
    x ^= x >> 30;
    x *= 0xBF58476D1CE4E5B9ULL;
    x ^= x >> 27;
    x *= 0x94D049BB133111EBULL;
    x ^= x >> 31;
    // 53-bit mantissa -> u in [0, total): never exactly total, so
    // lower_bound always lands inside the table.
    double u = static_cast<double>(x >> 11) *
               (1.0 / 9007199254740992.0) * total_;
    return static_cast<size_t>(
        std::lower_bound(cdf_.begin(), cdf_.end(), u) - cdf_.begin());
  }
  size_t keys() const { return cdf_.size(); }

 private:
  uint64_t seed_;
  std::vector<double> cdf_;
  double total_ = 0;
};

struct Shared {
  std::string tracker_host;
  int tracker_port = 0;
  std::atomic<int64_t> next{0};
  int64_t n_ops = 0;
  int64_t size = 0;
  int64_t unique = 0;  // 0 = every payload unique
  std::vector<std::string> ids;  // download/delete input
  std::unique_ptr<ZipfPicker> zipf;  // download key-popularity mode
  // Hot-set mode (--hot-keys K:pct): op i aims at one of the first
  // hot_keys ids with probability hot_frac, else uniformly at the
  // rest.  Hashed on the op index (deterministic regardless of thread
  // interleaving, the ZipfPicker discipline).
  int64_t hot_keys = 0;
  double hot_frac = 0;
  size_t HotPick(int64_t i, bool* hot) const {
    uint64_t x = 0x40fULL + 0x9E3779B97F4A7C15ULL *
                 (static_cast<uint64_t>(i) + 1);
    x ^= x >> 30;
    x *= 0xBF58476D1CE4E5B9ULL;
    x ^= x >> 27;
    x *= 0x94D049BB133111EBULL;
    x ^= x >> 31;
    double u = static_cast<double>(x >> 11) * (1.0 / 9007199254740992.0);
    uint64_t r = x * 0xD1342543DE82EF95ULL + 0x2545F4914F6CDD1DULL;
    size_t n = ids.size();
    size_t k = static_cast<size_t>(std::min<int64_t>(
        hot_keys, static_cast<int64_t>(n)));
    *hot = u < hot_frac && k > 0;
    if (*hot) return r % k;
    if (k >= n) {  // every id is hot: nothing cold to aim at
      *hot = true;
      return r % n;
    }
    return k + r % (n - k);
  }
  // Open-loop mode (--open-loop --rate R): op i is SCHEDULED at
  // t0 + i/R regardless of how slow earlier ops were, and its latency
  // clock starts at the scheduled time — so server-side queueing shows
  // up in the percentiles instead of silently throttling the offered
  // load (the coordinated-omission fix; closed-loop when rate == 0).
  double rate = 0;
  int64_t t0_us = 0;
  // Request QoS (--priority / --priority-mix): either one fixed class
  // for every op, or a weighted distribution op i is hashed onto
  // deterministically (thread-schedule independent, the ZipfPicker
  // discipline).  kUntagged = send no frame.
  int priority = kUntagged;
  std::vector<std::pair<int, double>> prio_cdf;  // (class, cumulative wt)
  int ClassFor(int64_t i) const {
    if (prio_cdf.empty()) return priority;
    uint64_t x = 0x5eedULL + 0x9E3779B97F4A7C15ULL *
                 (static_cast<uint64_t>(i) + 1);
    x ^= x >> 30;
    x *= 0xBF58476D1CE4E5B9ULL;
    x ^= x >> 27;
    x *= 0x94D049BB133111EBULL;
    x ^= x >> 31;
    double u = static_cast<double>(x >> 11) * (1.0 / 9007199254740992.0) *
               prio_cdf.back().second;
    for (const auto& [cls, acc] : prio_cdf)
      if (u < acc) return cls;
    return prio_cdf.back().first;
  }
  // Storage connections are drawn from this shared pool; --conns N
  // caps it (0 = unlimited).  Tracker connections stay per-worker —
  // they are tiny metadata RPCs and capping them would only measure
  // tracker queueing, not the storage-edge multiplexing this knob is
  // for.
  StoragePool pool;
  RankedMutex out_mu{LockRank::kToolOutput};
  std::vector<OpRecord> records;
};

// Open-loop gate for op i: sleep until its scheduled instant and return
// it as the latency-clock origin; closed-loop ops just start now.
int64_t OpStartUs(Shared* sh, int64_t i) {
  if (sh->rate <= 0) return MonoUs();
  int64_t sched = sh->t0_us +
                  static_cast<int64_t>(static_cast<double>(i) * 1e6 / sh->rate);
  int64_t now = MonoUs();
  if (now < sched)
    usleep(static_cast<useconds_t>(sched - now));
  return sched;
}

void Emit(Shared* sh, std::vector<OpRecord>* local) {
  std::lock_guard<RankedMutex> lk(sh->out_mu);
  for (auto& r : *local) sh->records.push_back(std::move(r));
  local->clear();
}

// Payload bytes for op i: xorshift stream seeded by the payload id, so
// two ops with the same id upload IDENTICAL bytes (dedup-able) without
// the driver storing any corpus in RAM.
void FillPayload(int64_t payload_id, std::string* buf) {
  uint64_t x = 0x9E3779B97F4A7C15ULL ^ (payload_id * 0xBF58476D1CE4E5B9ULL);
  if (x == 0) x = 1;
  for (size_t i = 0; i < buf->size(); i += 8) {
    x ^= x << 13;
    x ^= x >> 7;
    x ^= x << 17;
    size_t n = std::min<size_t>(8, buf->size() - i);
    memcpy(buf->data() + i, &x, n);
  }
}

void UploadWorker(Shared* sh) {
  Peer tracker(sh->tracker_host, sh->tracker_port);
  std::string payload(static_cast<size_t>(sh->size), '\0');
  std::vector<OpRecord> local;
  for (;;) {
    int64_t i = sh->next.fetch_add(1);
    if (i >= sh->n_ops) break;
    int64_t start = OpStartUs(sh, i);
    int64_t pid = sh->unique > 0 ? (i % sh->unique) : i;
    FillPayload(pid, &payload);
    // bytes stays 0 unless the daemon ACCEPTED the upload — failed ops
    // must not inflate combine's throughput.
    int cls = sh->ClassFor(i);
    OpRecord rec{start, 0, -1, 0, cls, ""};
    std::string group, ip;
    int port = 0;
    uint8_t spi = 0;
    if (QueryStore(&tracker, &group, &ip, &port, &spi)) {
      PooledPeer storage(&sh->pool, ip, port);
      // upload wire: 1B spi, 8B size, 6B ext, body
      std::string body;
      body.reserve(15 + payload.size());
      body.push_back(static_cast<char>(spi));
      uint8_t num[8];
      PutInt64BE(sh->size, num);
      body.append(reinterpret_cast<char*>(num), 8);
      body.append("bin\0\0\0", 6);
      body += payload;
      std::string resp;
      uint8_t status = 0;
      if (storage->Call(static_cast<uint8_t>(StorageCmd::kUploadFile), body,
                        &resp, &status, cls)) {
        rec.status = status;
        if (status == 0 && resp.size() > 16) {
          std::string g(resp.c_str(), strnlen(resp.c_str(), 16));
          rec.file_id = g + "/" + resp.substr(16);
          rec.bytes = sh->size;
        }
      }
    }
    rec.latency_us = MonoUs() - rec.start_us;
    local.push_back(std::move(rec));
    if (local.size() >= 1024) Emit(sh, &local);
  }
  Emit(sh, &local);
}

void DownloadWorker(Shared* sh) {
  Peer tracker(sh->tracker_host, sh->tracker_port);
  std::vector<OpRecord> local;
  for (;;) {
    int64_t i = sh->next.fetch_add(1);
    if (i >= sh->n_ops) break;
    int64_t start = OpStartUs(sh, i);
    std::string key_class;
    size_t pick;
    if (sh->hot_keys > 0) {
      bool hot = false;
      pick = sh->HotPick(i, &hot) % sh->ids.size();
      key_class = hot ? "hot" : "cold";
    } else if (sh->zipf != nullptr) {
      pick = sh->zipf->Pick(i) % sh->ids.size();
    } else {
      pick = static_cast<size_t>(i) % sh->ids.size();
    }
    const std::string& fid = sh->ids[pick];
    int cls = sh->ClassFor(i);
    OpRecord rec{start, 0, -1, 0, cls, fid, key_class};
    std::string ip;
    int port = 0;
    if (QueryFetch(&tracker,
                   static_cast<uint8_t>(TrackerCmd::kServiceQueryFetchOne),
                   fid, &ip, &port)) {
      PooledPeer storage(&sh->pool, ip, port);
      std::string group, remote;
      SplitId(fid, &group, &remote);
      uint8_t num[16] = {0};  // offset 0, length 0 (= to EOF)
      std::string body(reinterpret_cast<char*>(num), 16);
      body += PackGroup(group) + remote;
      std::string resp;
      uint8_t status = 0;
      if (storage->Call(static_cast<uint8_t>(StorageCmd::kDownloadFile),
                        body, &resp, &status, cls)) {
        rec.status = status;
        rec.bytes = static_cast<int64_t>(resp.size());
      }
    }
    rec.latency_us = MonoUs() - rec.start_us;
    local.push_back(std::move(rec));
    if (local.size() >= 1024) Emit(sh, &local);
  }
  Emit(sh, &local);
}

void DeleteWorker(Shared* sh) {
  Peer tracker(sh->tracker_host, sh->tracker_port);
  std::vector<OpRecord> local;
  for (;;) {
    int64_t i = sh->next.fetch_add(1);
    if (i >= static_cast<int64_t>(sh->ids.size())) break;
    const std::string& fid = sh->ids[i];
    int cls = sh->ClassFor(i);
    OpRecord rec{MonoUs(), 0, -1, 0, cls, fid};
    std::string ip;
    int port = 0;
    if (QueryFetch(&tracker,
                   static_cast<uint8_t>(TrackerCmd::kServiceQueryUpdate),
                   fid, &ip, &port)) {
      PooledPeer storage(&sh->pool, ip, port);
      std::string group, remote;
      SplitId(fid, &group, &remote);
      std::string resp;
      uint8_t status = 0;
      if (storage->Call(static_cast<uint8_t>(StorageCmd::kDeleteFile),
                        PackGroup(group) + remote, &resp, &status, cls))
        rec.status = status;
    }
    rec.latency_us = MonoUs() - rec.start_us;
    local.push_back(std::move(rec));
    if (local.size() >= 1024) Emit(sh, &local);
  }
  Emit(sh, &local);
}

bool WriteResults(const Shared& sh, const std::string& path, bool with_ids) {
  std::ofstream out(path);
  if (!out) return false;
  std::ofstream ids;
  if (with_ids) ids.open(path + ".ids");
  for (const auto& r : sh.records) {
    out << r.start_us << ' ' << r.latency_us << ' ' << r.status << ' '
        << r.bytes << ' ' << r.cls << ' ' << r.file_id;
    if (!r.key_class.empty()) out << ' ' << r.key_class;
    out << '\n';
    if (with_ids && r.status == 0 && !r.file_id.empty())
      ids << r.file_id << '\n';
  }
  return true;
}

bool LoadIds(const std::string& path, std::vector<std::string>* ids) {
  std::ifstream in(path);
  if (!in) return false;
  std::string line;
  while (std::getline(in, line))
    if (!line.empty()) ids->push_back(line);
  return !ids->empty();
}

int RunWorkers(Shared* sh, int threads, void (*fn)(Shared*)) {
  sh->t0_us = MonoUs();  // open-loop schedule origin
  std::vector<std::thread> ts;
  for (int t = 0; t < threads; ++t) ts.emplace_back(fn, sh);
  for (auto& t : ts) t.join();
  // Effective connection counts on stdout (records go to the result
  // file, so stdout is free): the harness asserts the topology it
  // asked for — e.g. `--conns 1` really did run one storage socket —
  // is the one the run actually had.
  sh->pool.PrintStats();
  return 0;
}

// Strip the mode-independent flags (valid anywhere after the mode
// word) out of argv, compacting the rest so positional parsing below
// stays oblivious: --open-loop / --rate R (--rate alone implies
// open-loop; --open-loop without a rate is an error rather than a
// guess) and --conns N (shared storage-connection budget).
bool StripGlobalFlags(int* argc, char** argv, Shared* sh) {
  bool open_loop = false;
  double rate = 0;
  int w = 0;
  for (int a = 0; a < *argc; ++a) {
    std::string flag = argv[a];
    if (flag == "--open-loop") {
      open_loop = true;
    } else if (flag == "--rate" && a + 1 < *argc) {
      char* end = nullptr;
      rate = strtod(argv[++a], &end);
      if (end == argv[a] || rate <= 0) {
        fprintf(stderr, "--rate wants a positive ops/sec, got %s\n", argv[a]);
        return false;
      }
    } else if (flag == "--conns" && a + 1 < *argc) {
      char* end = nullptr;
      long conns = strtol(argv[++a], &end, 10);
      if (end == argv[a] || conns < 0) {
        fprintf(stderr, "--conns wants a non-negative count, got %s\n",
                argv[a]);
        return false;
      }
      sh->pool.set_budget(static_cast<int>(conns));
    } else if (flag == "--priority" && a + 1 < *argc) {
      char* end = nullptr;
      long cls = strtol(argv[++a], &end, 10);
      if (end == argv[a] || cls < 0 || cls > 4) {
        fprintf(stderr, "--priority wants a class 0..4, got %s\n", argv[a]);
        return false;
      }
      sh->priority = static_cast<int>(cls);
    } else if (flag == "--priority-mix" && a + 1 < *argc) {
      // Comma-separated `[label:]class:weight` entries; a malformed
      // spec must be an ERROR, not a silent fall-through to untagged —
      // the per-class verdicts downstream would be measuring nothing.
      std::string spec = argv[++a];
      double acc = 0;
      size_t pos = 0;
      while (pos <= spec.size()) {
        size_t comma = spec.find(',', pos);
        std::string entry = spec.substr(
            pos, comma == std::string::npos ? std::string::npos : comma - pos);
        pos = comma == std::string::npos ? spec.size() + 1 : comma + 1;
        if (entry.empty()) continue;
        size_t c2 = entry.rfind(':');
        size_t c1 = c2 == std::string::npos ? std::string::npos
                                            : entry.rfind(':', c2 - 1);
        // label:class:weight has two colons; class:weight has one (then
        // c1 is npos and the class starts at 0).
        size_t cls_at = c1 == std::string::npos ? 0 : c1 + 1;
        char* end = nullptr;
        long cls = c2 == std::string::npos
                       ? -1
                       : strtol(entry.c_str() + cls_at, &end, 10);
        double wt = c2 == std::string::npos
                        ? 0
                        : strtod(entry.c_str() + c2 + 1, nullptr);
        if (cls < 0 || cls > 4 || end != entry.c_str() + c2 || wt <= 0) {
          fprintf(stderr,
                  "--priority-mix wants [label:]class:weight entries "
                  "(class 0..4, weight > 0), got %s\n", entry.c_str());
          return false;
        }
        acc += wt;
        sh->prio_cdf.emplace_back(static_cast<int>(cls), acc);
      }
      if (sh->prio_cdf.empty()) {
        fprintf(stderr, "--priority-mix spec is empty\n");
        return false;
      }
    } else {
      argv[w++] = argv[a];
    }
  }
  *argc = w;
  if (open_loop && rate <= 0) {
    fprintf(stderr, "--open-loop needs --rate <ops/sec>\n");
    return false;
  }
  sh->rate = rate;
  return true;
}

int64_t Pct(const std::vector<int64_t>& sorted, double q) {
  if (sorted.empty()) return 0;
  size_t i = std::min(static_cast<size_t>(q * sorted.size()),
                      sorted.size() - 1);
  return sorted[i];
}

const char* ClassName(int cls) {
  switch (cls) {
    case 0: return "control";
    case 1: return "interactive";
    case 2: return "normal";
    case 3: return "bulk";
    case 4: return "background";
    default: return "untagged";
  }
}

// combine: merge result files -> one JSON line (combine_result.c
// analogue).  QPS uses the union wall-clock window (min start .. max
// end) so multi-process runs aggregate honestly.  Records carry an
// optional priority-class column (older five-field files parse as
// untagged); "by_class" reports per-class admitted/shed splits (shed =
// the admission ladder's EBUSY 16) with latency percentiles over the
// ADMITTED ops — a shed answers in microseconds, and folding those
// into the percentiles would make an overloaded run look fast.
int Combine(int argc, char** argv) {
  struct ClassAgg {
    std::vector<int64_t> lat;  // admitted (status 0) only
    int64_t ops = 0, shed = 0, errors = 0;
  };
  std::vector<int64_t> lat;
  std::map<int, ClassAgg> by_class;
  std::map<std::string, ClassAgg> by_key_class;
  int64_t errors = 0, shed = 0, bytes = 0, t_min = INT64_MAX, t_max = 0;
  for (int a = 0; a < argc; ++a) {
    std::ifstream in(argv[a]);
    if (!in) {
      fprintf(stderr, "cannot open %s\n", argv[a]);
      return 1;
    }
    int64_t start, latency, b;
    int status;
    std::string rest;
    while (in >> start >> latency >> status >> b) {
      std::getline(in, rest);
      // Sniff the class column: a bare-integer first token is the
      // class, anything else (a file id, or nothing) is the legacy
      // five-field shape.
      int cls = kUntagged;
      size_t tok = rest.find_first_not_of(' ');
      if (tok != std::string::npos) {
        size_t end = rest.find(' ', tok);
        std::string first = rest.substr(
            tok, end == std::string::npos ? std::string::npos : end - tok);
        if (!first.empty() &&
            first.find_first_not_of("0123456789") == std::string::npos)
          cls = atoi(first.c_str());
      }
      // A trailing "hot"/"cold" token (--hot-keys runs) tags the key
      // class; anything else is an untagged record and contributes no
      // by_key_class row.
      std::string key_class;
      size_t last_end = rest.find_last_not_of(' ');
      if (last_end != std::string::npos) {
        size_t last_sp = rest.find_last_of(' ', last_end);
        std::string last_tok =
            rest.substr(last_sp + 1, last_end - last_sp);
        if (last_tok == "hot" || last_tok == "cold") key_class = last_tok;
      }
      lat.push_back(latency);
      auto& agg = by_class[cls];
      agg.ops++;
      if (status == 0) agg.lat.push_back(latency);
      else if (status == 16) { shed++; agg.shed++; errors++; }
      else { agg.errors++; errors++; }
      if (!key_class.empty()) {
        auto& kagg = by_key_class[key_class];
        kagg.ops++;
        if (status == 0) kagg.lat.push_back(latency);
        else if (status == 16) kagg.shed++;
        else kagg.errors++;
      }
      bytes += b;
      t_min = std::min(t_min, start);
      t_max = std::max(t_max, start + latency);
    }
  }
  if (lat.empty()) {
    printf("{\"ops\": 0}\n");
    return 0;
  }
  std::sort(lat.begin(), lat.end());
  double wall_s = static_cast<double>(t_max - t_min) / 1e6;
  int64_t sum = 0;
  for (int64_t v : lat) sum += v;
  std::string classes;
  for (auto& [cls, agg] : by_class) {
    std::sort(agg.lat.begin(), agg.lat.end());
    char buf[256];
    snprintf(buf, sizeof(buf),
             "%s\"%s\": {\"ops\": %lld, \"admitted\": %lld, "
             "\"shed\": %lld, \"errors\": %lld, \"lat_p50_us\": %lld, "
             "\"lat_p99_us\": %lld}",
             classes.empty() ? "" : ", ", ClassName(cls),
             static_cast<long long>(agg.ops),
             static_cast<long long>(agg.lat.size()),
             static_cast<long long>(agg.shed),
             static_cast<long long>(agg.errors),
             static_cast<long long>(Pct(agg.lat, 0.50)),
             static_cast<long long>(Pct(agg.lat, 0.99)));
    classes += buf;
  }
  // Per-key-class (hot/cold) percentiles: the headline number for the
  // elastic-replication bench is "hot-key p99 with promotion on vs
  // off", so the hot rows need their own latency distribution rather
  // than being smeared into the global percentiles.  Emitted only when
  // at least one record carried a key-class tag, so legacy runs keep
  // their exact JSON shape.
  std::string keyclasses;
  for (auto& [kc, agg] : by_key_class) {
    std::sort(agg.lat.begin(), agg.lat.end());
    char buf[320];
    snprintf(buf, sizeof(buf),
             "%s\"%s\": {\"ops\": %lld, \"admitted\": %lld, "
             "\"shed\": %lld, \"errors\": %lld, \"lat_p50_us\": %lld, "
             "\"lat_p95_us\": %lld, \"lat_p99_us\": %lld}",
             keyclasses.empty() ? "" : ", ", kc.c_str(),
             static_cast<long long>(agg.ops),
             static_cast<long long>(agg.lat.size()),
             static_cast<long long>(agg.shed),
             static_cast<long long>(agg.errors),
             static_cast<long long>(Pct(agg.lat, 0.50)),
             static_cast<long long>(Pct(agg.lat, 0.95)),
             static_cast<long long>(Pct(agg.lat, 0.99)));
    keyclasses += buf;
  }
  std::string key_section;
  if (!keyclasses.empty())
    key_section = ", \"by_key_class\": {" + keyclasses + "}";
  printf(
      "{\"ops\": %zu, \"errors\": %lld, \"shed\": %lld, "
      "\"wall_seconds\": %.3f, "
      "\"qps\": %.1f, \"bytes\": %lld, \"GBps\": %.4f, "
      "\"lat_mean_us\": %lld, \"lat_p50_us\": %lld, \"lat_p95_us\": %lld, "
      "\"lat_p99_us\": %lld, \"lat_max_us\": %lld, \"by_class\": {%s}%s}\n",
      lat.size(), static_cast<long long>(errors),
      static_cast<long long>(shed), wall_s,
      lat.size() / std::max(wall_s, 1e-9),
      static_cast<long long>(bytes),
      bytes / std::max(wall_s, 1e-9) / 1e9,
      static_cast<long long>(sum / static_cast<int64_t>(lat.size())),
      static_cast<long long>(Pct(lat, 0.50)),
      static_cast<long long>(Pct(lat, 0.95)),
      static_cast<long long>(Pct(lat, 0.99)),
      static_cast<long long>(lat.back()), classes.c_str(),
      key_section.c_str());
  return 0;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 2) {
    fprintf(stderr,
            "usage: fdfs_load upload|download|delete|combine|zipf-sample ...\n");
    return 2;
  }
  std::string mode = argv[1];
  if (mode == "combine") return Combine(argc - 2, argv + 2);
  if (mode == "zipf-sample" && (argc == 5 || argc == 6)) {
    double s = atof(argv[2]);
    int64_t keys = atoll(argv[3]);
    int64_t n = atoll(argv[4]);
    uint64_t seed = argc == 6 ? strtoull(argv[5], nullptr, 10) : 42;
    if (s <= 0 || keys <= 0 || n <= 0) {
      fprintf(stderr, "zipf-sample: s, keys, n must be positive\n");
      return 2;
    }
    ZipfPicker picker(s, static_cast<size_t>(keys), seed);
    for (int64_t i = 0; i < n; ++i)
      printf("%zu\n", picker.Pick(i));
    return 0;
  }

  Shared sh;
  if (!StripGlobalFlags(&argc, argv, &sh)) return 2;
  if (mode == "upload" && argc >= 7 &&
      std::string(argv[3]) == "--small-files") {
    // Small-file corpus mode (ISSUE 9): --small-files N
    // --file-bytes B <threads> <result>.  Every payload unique — the
    // worst case for per-object inodes, the best case for slabs.
    if (!SplitAddr(argv[2], &sh.tracker_host, &sh.tracker_port)) return 2;
    if (argc < 9 || std::string(argv[5]) != "--file-bytes") {
      fprintf(stderr,
              "usage: fdfs_load upload <tracker> --small-files N "
              "--file-bytes B <threads> <result>\n");
      return 2;
    }
    sh.n_ops = atoll(argv[4]);
    sh.size = atoll(argv[6]);
    if (sh.n_ops <= 0 || sh.size <= 0) {
      fprintf(stderr, "--small-files and --file-bytes must be positive\n");
      return 2;
    }
    int threads = atoi(argv[7]);
    sh.unique = 0;
    RunWorkers(&sh, threads, UploadWorker);
    return WriteResults(sh, argv[8], /*with_ids=*/true) ? 0 : 1;
  }
  if (mode == "upload" && argc >= 7) {
    if (!SplitAddr(argv[2], &sh.tracker_host, &sh.tracker_port)) return 2;
    sh.n_ops = atoll(argv[3]);
    sh.size = atoll(argv[4]);
    int threads = atoi(argv[5]);
    sh.unique = argc > 7 ? atoll(argv[7]) : 0;
    RunWorkers(&sh, threads, UploadWorker);
    return WriteResults(sh, argv[6], /*with_ids=*/true) ? 0 : 1;
  }
  if (mode == "download" && argc >= 7) {
    if (!SplitAddr(argv[2], &sh.tracker_host, &sh.tracker_port)) return 2;
    if (!LoadIds(argv[3], &sh.ids)) {
      fprintf(stderr, "no ids in %s\n", argv[3]);
      return 1;
    }
    sh.n_ops = atoll(argv[4]);
    int threads = atoi(argv[5]);
    // Optional key-popularity mode: --zipf <s> [--zipf-keys N]
    // [--zipf-seed S] after the positional args.
    double zipf_s = 0;
    int64_t zipf_keys = 0;
    uint64_t zipf_seed = 42;
    int64_t hot_keys = 0;
    double hot_pct = 0;
    for (int a = 7; a < argc; ++a) {
      std::string flag = argv[a];
      if (flag == "--hot-keys" && a + 1 < argc) {
        // Same error discipline as --zipf: a malformed spec must fail
        // loudly, not silently degrade to uniform traffic.
        std::string spec = argv[++a];
        size_t colon = spec.find(':');
        int64_t k = 0;
        double pct = 0;
        if (colon != std::string::npos) {
          k = strtoll(spec.c_str(), nullptr, 10);
          pct = strtod(spec.c_str() + colon + 1, nullptr);
        }
        if (colon == std::string::npos || k <= 0 || pct <= 0 ||
            pct > 100) {
          fprintf(stderr,
                  "--hot-keys wants K:pct with K>0 and 0<pct<=100, got %s\n",
                  spec.c_str());
          return 2;
        }
        hot_keys = k;
        hot_pct = pct;
      } else if (flag == "--zipf" && a + 1 < argc) {
        // A bad exponent must be an ERROR, not a silent fall-through to
        // round-robin: this flag exists to measure skew, and "measured
        // unskewed traffic believing it was zipfian" poisons the
        // harness verdicts downstream.
        char* end = nullptr;
        zipf_s = strtod(argv[++a], &end);
        if (end == argv[a] || zipf_s <= 0) {
          fprintf(stderr, "--zipf wants a positive exponent, got %s\n",
                  argv[a]);
          return 2;
        }
      } else if (flag == "--zipf-keys" && a + 1 < argc) {
        zipf_keys = atoll(argv[++a]);
      } else if (flag == "--zipf-seed" && a + 1 < argc) {
        zipf_seed = strtoull(argv[++a], nullptr, 10);
      } else {
        fprintf(stderr, "bad download flag %s\n", flag.c_str());
        return 2;
      }
    }
    if (hot_keys > 0 && zipf_s > 0) {
      fprintf(stderr, "--hot-keys and --zipf are mutually exclusive\n");
      return 2;
    }
    if (hot_keys > 0) {
      sh.hot_keys = hot_keys;
      sh.hot_frac = hot_pct / 100.0;
    }
    if (zipf_s > 0) {
      size_t universe = static_cast<size_t>(
          zipf_keys > 0 ? zipf_keys : std::min<int64_t>(1000, sh.ids.size()));
      if (universe > sh.ids.size()) universe = sh.ids.size();
      sh.zipf = std::make_unique<ZipfPicker>(zipf_s, universe, zipf_seed);
    }
    RunWorkers(&sh, threads, DownloadWorker);
    return WriteResults(sh, argv[6], /*with_ids=*/false) ? 0 : 1;
  }
  if (mode == "delete" && argc >= 6) {
    if (!SplitAddr(argv[2], &sh.tracker_host, &sh.tracker_port)) return 2;
    if (!LoadIds(argv[3], &sh.ids)) {
      fprintf(stderr, "no ids in %s\n", argv[3]);
      return 1;
    }
    sh.n_ops = static_cast<int64_t>(sh.ids.size());
    int threads = atoi(argv[4]);
    RunWorkers(&sh, threads, DeleteWorker);
    return WriteResults(sh, argv[5], /*with_ids=*/false) ? 0 : 1;
  }
  fprintf(stderr, "bad arguments for %s\n", mode.c_str());
  return 2;
}
