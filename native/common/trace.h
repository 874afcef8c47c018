// Distributed request tracing: trace-context parsing and a lock-light
// fixed-size span ring buffer — the per-daemon half of the tracing
// pipeline (the Python half lives in fastdfs_tpu/trace.py).
//
// Wire contract (fastdfs_tpu.common.protocol): a traced request is
// prefixed by one TRACE_CTX frame — a normal 10-byte header with
// cmd=kTraceCtx and pkg_len=kTraceCtxLen whose body is 8B trace_id +
// 4B parent span_id + 4B flags, all big-endian.  The frame elicits no
// response; the daemon applies the context to the NEXT request on the
// connection.  An untraced request is byte-identical to the pre-trace
// protocol (append-only interop: old daemons/clients work untraced).
//
// Reference departure: upstream FastDFS has no request tracing at all —
// its access log records only per-request totals.  Aggregate histograms
// (stats.h, PR 1) cannot attribute ONE slow upload to CDC vs dio vs
// binlog vs the replication hop; spans can.
//
// Concurrency: Record() claims a slot with a fetch_add and takes a
// per-slot spinlock (acquire/release atomics, so TSan sees the
// happens-before) only for the memcpy-sized critical section; Json()
// takes each slot's lock briefly while copying.  No global lock, no
// allocation on the record path.
#pragma once

#include <atomic>
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>

#include "common/lockrank.h"
#include <string>
#include <vector>

namespace fdfs {

// Decoded TRACE_CTX frame body.  trace_id 0 == "no context".
struct TraceCtx {
  uint64_t trace_id = 0;
  uint32_t parent_span = 0;
  uint32_t flags = 0;
  bool valid() const { return trace_id != 0; }
};

constexpr uint32_t kTraceFlagSampled = 1;  // client asked for the trace
constexpr uint32_t kTraceFlagSlow = 2;     // force-retained by slow gate

TraceCtx ParseTraceCtx(const uint8_t* p);          // reads kTraceCtxLen bytes
void SerializeTraceCtx(const TraceCtx& c, uint8_t* out);  // writes 16 bytes

// The full on-wire prefix frame (header with cmd=kTraceCtx + 16B body);
// out must hold kTraceCtxFrameLen bytes.  The single place the frame
// layout lives — every native sender (replication, recovery) uses it.
constexpr int kTraceCtxFrameLen = 10 /*kHeaderSize*/ + 16 /*kTraceCtxLen*/;
void BuildTraceCtxFrame(const TraceCtx& c, uint8_t* out);

// Wall-clock microseconds (CLOCK_REALTIME): spans from different nodes
// must share a clock domain to stitch into one timeline.
int64_t TraceWallUs();

struct TraceSpan {
  uint64_t trace_id = 0;
  uint32_t span_id = 0;
  uint32_t parent_id = 0;
  int64_t start_us = 0;   // wall-clock epoch µs
  int64_t dur_us = 0;
  int32_t status = 0;     // errno-style response status (0 = OK)
  uint32_t flags = 0;
  char name[40] = {0};    // NUL-terminated stage name, e.g. "storage.upload_file"

  void SetName(const char* n) {
    std::strncpy(name, n, sizeof(name) - 1);
    name[sizeof(name) - 1] = '\0';
  }
};

// -- per-request stage intervals -----------------------------------------
//
// What one request did, stage by stage, as it happened: every stage of
// the upload path opens an interval where its work starts and closes it
// where it ends (StageScope), once per segment and stage, never per
// chunk.  The recorder belongs to the request (StorageServer::Conn) and
// is written by whichever single thread works on the request at the time:
// no lock, no allocation, no system call, only the MonoUs() reads.  The
// access log's stage columns and the ingest histograms are the sums kept
// here; the span ring (a traced or slow request) and the access log's
// "stages" line (use_access_log) are two sinks for the same intervals.
enum class Stage : uint8_t {
  kRecv,         // storage.recv: header parsed -> body received
  kDioWait,      // dio.queue_wait: submitted -> picked up by a worker
  kReadback,     // storage.tmp_readback: one segment of the tmp file read
  kFingerprint,  // storage.fingerprint: one FingerprintChunks call
  kCdc,          // storage.cdc: the native chunker inside it
  kFpLock,       // storage.fp_lock: wait for a pooled sidecar connection
  kFpRpc,        // storage.fp_rpc: first byte sent -> reply read
  kCsWrite,      // storage.cs_write: chunk-store writes
  kNegotiate,    // storage.negotiate: UPLOAD_RECIPE parse + PinAndMask
  kVerify,       // storage.commit.verify: shipped chunks of one segment
  kPresent,      // storage.commit.present: present chunks of one segment
  kRecipe,       // storage.commit.recipe: a commit's id mint + recipe write
  kReindex,      // storage.reindex: a commit's fingerprint of one segment
  kBinlog,       // storage.binlog: the binlog append
  kEcWrite,      // storage.ec_write: a segment's new chunks into a stripe
  kCount
};
const char* StageName(Stage s);

struct StageTrace {
  static constexpr int kCapacity = 48;  // a two-segment commit records ~20
  struct Interval {
    int64_t start_us;  // MonoUs() stamps
    int64_t end_us;
    int64_t args[2];   // meaning by stage: StageArgNames (else unused)
    Stage stage;
    int8_t parent;     // index of the enclosing interval, -1 = the request
  };
  Interval iv[kCapacity];
  int64_t sum_us[static_cast<int>(Stage::kCount)] = {0};
  int8_t n = 0;
  int8_t open = -1;        // innermost interval still open
  bool truncated = false;  // an interval did not fit (the sums hold it)

  int64_t Sum(Stage s) const { return sum_us[static_cast<int>(s)]; }
  void Reset() {
    std::memset(sum_us, 0, sizeof(sum_us));
    n = 0;
    open = -1;
    truncated = false;
  }
  // An interval whose two ends are known already (the body's receive,
  // the dio queue wait); nests under whatever is open.
  void Add(Stage s, int64_t start_us, int64_t end_us);
};

// The two argument names of a stage's intervals (nullptr = none):
// storage.fp_rpc carries the session and base_offset of its request body,
// storage.commit.present the chunks its batched read served and the
// preadv calls that took.
const char* const* StageArgNames(Stage s);

// The recorder of the request this thread is working on, for code that
// has no Conn at hand (dedup.cc; ingest.cc).  Null outside a
// request's dio work (recovery, replication senders): StageScope then
// does nothing.
StageTrace* CurrentStageTrace();
class StageTraceBinding {  // sets it for a scope
 public:
  explicit StageTraceBinding(StageTrace* t);
  ~StageTraceBinding();
  StageTraceBinding(const StageTraceBinding&) = delete;
  StageTraceBinding& operator=(const StageTraceBinding&) = delete;

 private:
  StageTrace* prev_;
};

// Opens an interval now, closes it at End() or scope exit.
class StageScope {
 public:
  StageScope(StageTrace* t, Stage s);
  ~StageScope() { End(); }
  StageScope(const StageScope&) = delete;
  StageScope& operator=(const StageScope&) = delete;
  void SetArgs(int64_t arg0, int64_t arg1);
  void End();

 private:
  StageTrace* t_;
  int64_t start_us_;
  Stage stage_;
  int8_t idx_;  // -1: did not fit (or no recorder)
};

// The access log's line for one request's intervals: compact JSON, no
// spaces (a single token to every column parser), no newline:
//   {"event":"stages","cmd":11,"status":0,"t0_mono_us":..,"t0_wall_us":..,
//    "dur_us":..,"truncated":0,"spans":[[name,start_offset_us,dur_us,
//    parent_index(,{"arg":value,..})],..]}
// Offsets are from t0_mono_us, the request's first MonoUs() stamp.
std::string StageLineJson(const StageTrace& t, int cmd, int status,
                          int64_t t0_mono_us, int64_t t0_wall_us,
                          int64_t dur_us);

class TraceRing {
 public:
  explicit TraceRing(size_t capacity);

  // Process-unique (per ring) nonzero span id.
  uint32_t NextSpanId() { return next_span_.fetch_add(1) | 0x80000000u; }
  // Fresh trace id for daemon-originated traces (slow-request retention,
  // recovery sessions): wall-time salted with the span counter so two
  // daemons starting the same second do not collide in practice.
  uint64_t NewTraceId();

  void Record(const TraceSpan& s);

  // JSON dump: {"role":"...","port":N,"spans":[...]} — spans sorted by
  // start_us, trace/span ids as fixed-width hex strings (JSON numbers
  // lose 64-bit precision in some decoders).
  std::string Json(const std::string& role, int port) const;

  int64_t recorded() const { return recorded_.load(); }
  // Spans overwritten before any dump (ring wrapped past them).
  int64_t dropped() const {
    int64_t r = recorded_.load();
    return r > static_cast<int64_t>(cap_) ? r - static_cast<int64_t>(cap_) : 0;
  }
  size_t capacity() const { return cap_; }

 private:
  struct Slot {
    RankedSpinLock lock{LockRank::kTraceSlot};
    bool used = false;
    TraceSpan span;
  };

  size_t cap_;
  std::unique_ptr<Slot[]> slots_;
  std::atomic<uint64_t> head_{0};
  std::atomic<int64_t> recorded_{0};
  std::atomic<uint32_t> next_span_{1};
};

// One structured slow-request line: compact JSON (no spaces — the plain
// access-log parser then skips it as a single token while
// tools/access_log_stages.py --slow ingests it).
std::string SlowRequestJson(const std::string& role, const char* op,
                            const TraceSpan& root, const std::string& peer,
                            int64_t bytes);

// Bounded remote-filename -> TraceCtx map: remembers which recent
// mutations were traced so the replication sender can propagate the
// context onto the sync hop (the binlog format stays untouched).  A
// record evicted before its sync ships simply replicates untraced —
// tracing is best-effort observability, not a durability feature.
class TraceCorrelator {
 public:
  explicit TraceCorrelator(size_t max_entries = 1024) : max_(max_entries) {}

  void Put(const std::string& remote, const TraceCtx& ctx);
  // Returns and ERASES the entry (one sync hop per peer would need
  // per-peer copies; the first shipper wins — enough to stitch the
  // acceptance path, and the map stays bounded under load).
  bool Take(const std::string& remote, TraceCtx* out);
  size_t size() const;

 private:
  mutable RankedMutex mu_{LockRank::kTraceCorrelator};
  size_t max_;
  uint64_t seq_ = 0;
  std::map<std::string, std::pair<TraceCtx, uint64_t>> entries_;
};

}  // namespace fdfs
