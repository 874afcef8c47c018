#include "common/trace.h"

#include <time.h>

#include <algorithm>
#include <cstdio>

#include "common/bytes.h"
#include "common/net.h"
#include "common/protocol_gen.h"
#include "common/threadreg.h"

namespace fdfs {

static_assert(kTraceCtxLen == 16, "TraceCtx wire layout is 8+4+4 bytes");

TraceCtx ParseTraceCtx(const uint8_t* p) {
  TraceCtx c;
  c.trace_id = static_cast<uint64_t>(GetInt64BE(p));
  c.parent_span = (static_cast<uint32_t>(p[8]) << 24) |
                  (static_cast<uint32_t>(p[9]) << 16) |
                  (static_cast<uint32_t>(p[10]) << 8) |
                  static_cast<uint32_t>(p[11]);
  c.flags = (static_cast<uint32_t>(p[12]) << 24) |
            (static_cast<uint32_t>(p[13]) << 16) |
            (static_cast<uint32_t>(p[14]) << 8) |
            static_cast<uint32_t>(p[15]);
  return c;
}

void SerializeTraceCtx(const TraceCtx& c, uint8_t* out) {
  PutInt64BE(static_cast<int64_t>(c.trace_id), out);
  out[8] = static_cast<uint8_t>(c.parent_span >> 24);
  out[9] = static_cast<uint8_t>(c.parent_span >> 16);
  out[10] = static_cast<uint8_t>(c.parent_span >> 8);
  out[11] = static_cast<uint8_t>(c.parent_span);
  out[12] = static_cast<uint8_t>(c.flags >> 24);
  out[13] = static_cast<uint8_t>(c.flags >> 16);
  out[14] = static_cast<uint8_t>(c.flags >> 8);
  out[15] = static_cast<uint8_t>(c.flags);
}

void BuildTraceCtxFrame(const TraceCtx& c, uint8_t* out) {
  static_assert(kTraceCtxFrameLen == kHeaderSize + kTraceCtxLen,
                "frame = header + ctx body");
  PutInt64BE(kTraceCtxLen, out);
  out[8] = static_cast<uint8_t>(StorageCmd::kTraceCtx);  // == TrackerCmd's
  out[9] = 0;
  SerializeTraceCtx(c, out + kHeaderSize);
}

int64_t TraceWallUs() {
  struct timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000 + ts.tv_nsec / 1000;
}

// -- per-request stage intervals -----------------------------------------

const char* StageName(Stage s) {
  static const char* const kNames[] = {
      "storage.recv",          "dio.queue_wait",
      "storage.tmp_readback",  "storage.fingerprint",
      "storage.cdc",           "storage.fp_lock",
      "storage.fp_rpc",        "storage.cs_write",
      "storage.negotiate",     "storage.commit.verify",
      "storage.commit.present", "storage.commit.recipe",
      "storage.reindex",       "storage.binlog"};
  static_assert(sizeof(kNames) / sizeof(kNames[0]) ==
                    static_cast<size_t>(Stage::kCount),
                "one name per stage");
  return kNames[static_cast<int>(s)];
}

const char* const* StageArgNames(Stage s) {
  static const char* const kFpRpc[2] = {"session", "base_offset"};
  static const char* const kPresent[2] = {"read_chunks", "read_batches"};
  switch (s) {
    case Stage::kFpRpc:
      return kFpRpc;
    case Stage::kPresent:
      return kPresent;
    default:
      return nullptr;
  }
}

void StageTrace::Add(Stage s, int64_t start_us, int64_t end_us) {
  sum_us[static_cast<int>(s)] += end_us - start_us;
  if (n >= kCapacity) {
    truncated = true;
    return;
  }
  iv[n++] = Interval{start_us, end_us, {0, 0}, s, open};
}

static thread_local StageTrace* tls_stage_trace = nullptr;

StageTrace* CurrentStageTrace() { return tls_stage_trace; }

StageTraceBinding::StageTraceBinding(StageTrace* t) : prev_(tls_stage_trace) {
  tls_stage_trace = t;
}

StageTraceBinding::~StageTraceBinding() { tls_stage_trace = prev_; }

StageScope::StageScope(StageTrace* t, Stage s)
    : t_(t), start_us_(0), stage_(s), idx_(-1) {
  if (t_ == nullptr) return;
  start_us_ = MonoUs();
  if (t_->n >= StageTrace::kCapacity) return;  // End() keeps the sum
  idx_ = t_->n++;
  t_->iv[idx_] =
      StageTrace::Interval{start_us_, start_us_, {0, 0}, s, t_->open};
  t_->open = idx_;
}

void StageScope::SetArgs(int64_t arg0, int64_t arg1) {
  if (idx_ < 0) return;
  t_->iv[idx_].args[0] = arg0;
  t_->iv[idx_].args[1] = arg1;
}

void StageScope::End() {
  if (t_ == nullptr) return;
  const int64_t now = MonoUs();
  t_->sum_us[static_cast<int>(stage_)] += now - start_us_;
  if (idx_ >= 0) {
    t_->iv[idx_].end_us = now;
    t_->open = t_->iv[idx_].parent;
  } else {
    t_->truncated = true;
  }
  t_ = nullptr;
}

std::string StageLineJson(const StageTrace& t, int cmd, int status,
                          int64_t t0_mono_us, int64_t t0_wall_us,
                          int64_t dur_us) {
  char buf[256];
  std::snprintf(buf, sizeof(buf),
                "{\"event\":\"stages\",\"cmd\":%d,\"status\":%d,"
                "\"t0_mono_us\":%lld,\"t0_wall_us\":%lld,\"dur_us\":%lld,"
                "\"truncated\":%d,\"spans\":[",
                cmd, status, static_cast<long long>(t0_mono_us),
                static_cast<long long>(t0_wall_us),
                static_cast<long long>(dur_us), t.truncated ? 1 : 0);
  std::string out = buf;
  out.reserve(out.size() + static_cast<size_t>(t.n) * 64 + 2);
  for (int i = 0; i < t.n; ++i) {
    const StageTrace::Interval& v = t.iv[i];
    // Escape-free by construction: names come from compile-time tables.
    std::snprintf(buf, sizeof(buf), "%s[\"%s\",%lld,%lld,%d", i ? "," : "",
                  StageName(v.stage),
                  static_cast<long long>(v.start_us - t0_mono_us),
                  static_cast<long long>(v.end_us - v.start_us), v.parent);
    out += buf;
    if (const char* const* names = StageArgNames(v.stage)) {
      std::snprintf(buf, sizeof(buf), ",{\"%s\":%lld,\"%s\":%lld}", names[0],
                    static_cast<long long>(v.args[0]), names[1],
                    static_cast<long long>(v.args[1]));
      out += buf;
    }
    out += "]";
  }
  out += "]}";
  return out;
}

TraceRing::TraceRing(size_t capacity)
    : cap_(capacity == 0 ? 1 : capacity), slots_(new Slot[cap_]) {
  // Salt the span-id base per ring: every daemon allocates from the same
  // 31-bit space (the high bit marks daemon ids vs client ids), and two
  // daemons counting up from 1 would collide on every id — colliding
  // span ids inside one trace corrupt the parent/child stitch.
  next_span_.store(
      static_cast<uint32_t>(static_cast<uint64_t>(TraceWallUs()) *
                            2654435761ULL) |
      1u);
}

uint64_t TraceRing::NewTraceId() {
  uint64_t id = (static_cast<uint64_t>(TraceWallUs()) << 16) ^
                (next_span_.fetch_add(1) * 0x9E3779B97F4A7C15ULL);
  return id == 0 ? 1 : id;
}

void TraceRing::Record(const TraceSpan& s) {
  size_t idx = static_cast<size_t>(head_.fetch_add(1)) % cap_;
  Slot* slot = &slots_[idx];
  SpinGuard guard(slot->lock);
  slot->span = s;
  slot->used = true;
  recorded_.fetch_add(1, std::memory_order_relaxed);
}

std::string TraceRing::Json(const std::string& role, int port) const {
  std::vector<TraceSpan> spans;
  spans.reserve(cap_);
  for (size_t i = 0; i < cap_; ++i) {
    Slot* slot = &slots_[i];
    SpinGuard guard(slot->lock);
    if (slot->used) spans.push_back(slot->span);
  }
  std::sort(spans.begin(), spans.end(),
            [](const TraceSpan& a, const TraceSpan& b) {
              return a.start_us != b.start_us ? a.start_us < b.start_us
                                              : a.span_id < b.span_id;
            });
  std::string out = "{\"role\":\"" + role + "\",\"port\":" +
                    std::to_string(port) + ",\"spans\":[";
  char buf[256];
  for (size_t i = 0; i < spans.size(); ++i) {
    const TraceSpan& s = spans[i];
    if (i) out += ",";
    // Escape-free by construction: names come from compile-time tables.
    std::snprintf(buf, sizeof(buf),
                  "{\"trace_id\":\"%016llx\",\"span_id\":\"%08x\","
                  "\"parent_id\":\"%08x\",\"name\":\"%s\","
                  "\"start_us\":%lld,\"dur_us\":%lld,\"status\":%d,"
                  "\"flags\":%u}",
                  static_cast<unsigned long long>(s.trace_id), s.span_id,
                  s.parent_id, s.name, static_cast<long long>(s.start_us),
                  static_cast<long long>(s.dur_us), s.status, s.flags);
    out += buf;
  }
  out += "]}";
  return out;
}

std::string SlowRequestJson(const std::string& role, const char* op,
                            const TraceSpan& root, const std::string& peer,
                            int64_t bytes) {
  // Emitted on the handling thread, so the ledger name identifies WHICH
  // nio loop / dio worker served the slow request — cross-reference
  // against thread.<name>.cpu_pct to tell "this loop is saturated" from
  // "this one request was slow".
  const char* thread = CurrentThreadName();
  char buf[448];
  std::snprintf(buf, sizeof(buf),
                "{\"event\":\"slow_request\",\"role\":\"%s\",\"op\":\"%s\","
                "\"trace_id\":\"%016llx\",\"span_id\":\"%08x\","
                "\"start_us\":%lld,\"dur_us\":%lld,\"status\":%d,"
                "\"peer\":\"%s\",\"bytes\":%lld,\"thread\":\"%s\"}",
                role.c_str(), op,
                static_cast<unsigned long long>(root.trace_id), root.span_id,
                static_cast<long long>(root.start_us),
                static_cast<long long>(root.dur_us), root.status,
                peer.c_str(), static_cast<long long>(bytes),
                thread[0] != '\0' ? thread : "unnamed");
  return buf;
}

void TraceCorrelator::Put(const std::string& remote, const TraceCtx& ctx) {
  std::lock_guard<RankedMutex> lk(mu_);
  if (entries_.size() >= max_ && entries_.find(remote) == entries_.end()) {
    // Evict the oldest entry (smallest sequence stamp): a stale traced
    // mutation whose sync never shipped should yield to fresh ones.
    auto oldest = entries_.begin();
    for (auto it = entries_.begin(); it != entries_.end(); ++it)
      if (it->second.second < oldest->second.second) oldest = it;
    entries_.erase(oldest);
  }
  entries_[remote] = {ctx, ++seq_};
}

bool TraceCorrelator::Take(const std::string& remote, TraceCtx* out) {
  std::lock_guard<RankedMutex> lk(mu_);
  auto it = entries_.find(remote);
  if (it == entries_.end()) return false;
  *out = it->second.first;
  entries_.erase(it);
  return true;
}

size_t TraceCorrelator::size() const {
  std::lock_guard<RankedMutex> lk(mu_);
  return entries_.size();
}

}  // namespace fdfs
