#include "storage/dedup.h"

#include <string.h>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstdio>

#include "common/bytes.h"
#include "common/cdc.h"
#include "common/gear_gen.h"
#include "common/log.h"
#include "common/net.h"
#include "common/protocol_gen.h"
#include "common/trace.h"

namespace fdfs {

// -- CpuDedup -------------------------------------------------------------

CpuDedup::CpuDedup(std::string snapshot_path, CdcWidths widths)
    : snapshot_path_(std::move(snapshot_path)), widths_(widths) {}

DedupPlugin::Verdict CpuDedup::Judge(const std::string& sha1_hex, int64_t) {
  Verdict v;
  std::lock_guard<RankedMutex> lk(mu_);
  auto it = by_digest_.find(sha1_hex);
  if (it != by_digest_.end()) {
    v.duplicate = true;
    v.dup_of = it->second;
  }
  return v;
}

void CpuDedup::Commit(const std::string& sha1_hex, const std::string& file_id) {
  std::lock_guard<RankedMutex> lk(mu_);
  by_digest_.emplace(sha1_hex, file_id);  // first writer wins
  by_file_[file_id] = sha1_hex;
}

void CpuDedup::Forget(const std::string& file_id) {
  std::lock_guard<RankedMutex> lk(mu_);
  auto it = by_file_.find(file_id);
  if (it == by_file_.end()) return;
  auto dit = by_digest_.find(it->second);
  // Only drop the digest entry if it still names this file (another file
  // with identical bytes may have replaced it as the canonical copy).
  if (dit != by_digest_.end() && dit->second == file_id) by_digest_.erase(dit);
  by_file_.erase(it);
}

bool CpuDedup::Save() {
  std::lock_guard<RankedMutex> lk(mu_);
  std::string tmp = snapshot_path_ + ".tmp";
  FILE* f = fopen(tmp.c_str(), "w");
  if (f == nullptr) return false;
  for (const auto& [digest, id] : by_digest_)
    fprintf(f, "%s %s\n", digest.c_str(), id.c_str());
  fclose(f);
  return rename(tmp.c_str(), snapshot_path_.c_str()) == 0;
}

// The native chunker as its own interval (storage.cdc) of the request
// this thread works on: both plugins cut with it, and the access log's
// cdc_us column says how much of fp_us it was.
static std::vector<int64_t> TimedGearChunkStream(const char* data,
                                                 size_t len,
                                                 const CdcWidths& w) {
  StageScope cdc(CurrentStageTrace(), Stage::kCdc);
  return GearChunkStream(reinterpret_cast<const uint8_t*>(data), len,
                         w.min_size, w.avg_bits, w.max_size);
}

bool CpuDedup::FingerprintChunks(int64_t /*session*/, const char* data,
                                 size_t len, int64_t base_offset,
                                 std::vector<ChunkFp>* out) {
  std::vector<int64_t> cuts = TimedGearChunkStream(data, len, widths_);
  int64_t last = 0;
  for (int64_t cut : cuts) {
    ChunkFp fp;
    fp.offset = base_offset + last;
    fp.length = cut - last;
    fp.digest_hex = Sha1(data + last, static_cast<size_t>(cut - last)).Hex();
    out->push_back(std::move(fp));
    last = cut;
  }
  return true;
}

bool CpuDedup::LoadSnapshot() {
  FILE* f = fopen(snapshot_path_.c_str(), "r");
  if (f == nullptr) return true;  // no snapshot yet
  char digest[64], id[512];
  while (fscanf(f, "%63s %511s", digest, id) == 2) {
    by_digest_[digest] = id;
    by_file_[id] = digest;
  }
  fclose(f);
  FDFS_LOG_INFO("dedup(cpu): loaded %zu digests from snapshot",
                by_digest_.size());
  return true;
}

// -- SidecarDedup ---------------------------------------------------------

SidecarDedup::SidecarDedup(std::string socket_path, int max_idle_fds,
                           CdcWidths widths)
    : socket_path_(std::move(socket_path)),
      max_idle_fds_(std::max(max_idle_fds, kMinIdleFds)),
      widths_(widths) {}

SidecarDedup::~SidecarDedup() {
  for (int fd : pool_) close(fd);
}

int SidecarDedup::AcquireFd(bool* pooled, StageTrace* stages) {
  {
    // Only the pool-mutex wait counts as "lock wait" (storage.fp_lock, of
    // a fingerprint RPC) — connection setup below is transport cost, not
    // serialization.
    StageScope wait(stages, Stage::kFpLock);
    std::lock_guard<RankedMutex> lk(mu_);
    wait.End();
    if (!pool_.empty()) {
      int fd = pool_.back();
      pool_.pop_back();
      *pooled = true;
      return fd;
    }
  }
  *pooled = false;
  int fd = socket(AF_UNIX, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  struct sockaddr_un addr;
  memset(&addr, 0, sizeof(addr));
  addr.sun_family = AF_UNIX;
  strncpy(addr.sun_path, socket_path_.c_str(), sizeof(addr.sun_path) - 1);
  if (connect(fd, reinterpret_cast<struct sockaddr*>(&addr), sizeof(addr)) != 0 ||
      !Handshake(fd)) {
    close(fd);
    return -1;
  }
  return fd;
}

// Tell the sidecar the widths this daemon cuts with.  Its engine plans
// tiles for, and its indexes hold chunks of, exactly one set of widths; a
// sidecar at another set answers an error, and this connection is then
// never used: the upload falls to the flat path (fail-open) with the
// reason in the log, and nothing is stored under mixed widths.
bool SidecarDedup::Handshake(int fd) {
  const int timeout_ms = 60000;
  const std::string body = "widths " + std::to_string(widths_.min_size) + " " +
                           std::to_string(widths_.avg_bits) + " " +
                           std::to_string(widths_.max_size);
  uint8_t hdr[kHeaderSize];
  PutInt64BE(static_cast<int64_t>(body.size()), hdr);
  hdr[8] = static_cast<uint8_t>(StorageCmd::kDedupCommit);
  hdr[9] = 0;
  if (!SendAll(fd, hdr, sizeof(hdr), timeout_ms) ||
      !SendAll(fd, body.data(), body.size(), timeout_ms) ||
      !RecvAll(fd, hdr, sizeof(hdr), timeout_ms))
    return false;
  const int64_t len = GetInt64BE(hdr);
  if (len < 0 || len > 4096) return false;
  std::string why(static_cast<size_t>(len), '\0');
  if (len > 0 && !RecvAll(fd, why.data(), why.size(), timeout_ms)) return false;
  if (hdr[9] != 0) {
    FDFS_LOG_ERROR(
        "dedup(sidecar): REFUSED at dedup_cdc_widths %lld:%d:%lld (status %d): "
        "%s -- start the sidecar with the same --cdc-widths",
        static_cast<long long>(widths_.min_size), widths_.avg_bits,
        static_cast<long long>(widths_.max_size), hdr[9], why.c_str());
    return false;
  }
  return true;
}

void SidecarDedup::ReleaseFd(int fd) {
  std::lock_guard<RankedMutex> lk(mu_);
  if (static_cast<int>(pool_.size()) >= max_idle_fds_) {
    close(fd);
    return;
  }
  pool_.push_back(fd);
}

bool SidecarDedup::Rpc(uint8_t cmd, const std::string& body, std::string* resp,
                       uint8_t* status, int64_t max_resp, const char* tail,
                       size_t tail_len, const int64_t* fp_rpc_args) {
  // Each RPC borrows its own pooled connection, so concurrent dio
  // threads overlap their sidecar round-trips.  A failure on a POOLED
  // fd retries once on a fresh connection: after a sidecar restart the
  // pool holds up to max_idle_fds_ dead sockets, and without the retry
  // each of those would fail one upload into the flat-store path.  The
  // request is header + body + tail, each sent from where it lies, and a
  // retry sends all three again.  A fingerprint RPC (fp_rpc_args: the
  // session and base_offset its body carries, by which the sidecar's
  // fdfs.sidecar.request span of the same RPC is found) is an interval of
  // the request this thread works on, storage.fp_rpc, from the first byte
  // sent to the reply read; the wait for the connection lies before it.
  const int timeout_ms = 60000;
  StageTrace* const stages =
      fp_rpc_args != nullptr ? CurrentStageTrace() : nullptr;
  for (int attempt = 0; attempt < 2; ++attempt) {
    bool pooled = false;
    int fd = AcquireFd(&pooled, stages);
    if (fd < 0) return false;
    StageScope rpc(stages, Stage::kFpRpc);
    if (stages != nullptr) rpc.SetArgs(fp_rpc_args[0], fp_rpc_args[1]);
    uint8_t hdr[kHeaderSize];
    PutInt64BE(static_cast<int64_t>(body.size() + tail_len), hdr);
    hdr[8] = cmd;
    hdr[9] = 0;
    // Generous timeout for fingerprint segments (first TPU compile of a
    // new bucket shape can take tens of seconds); the rest is instant.
    if (!SendAll(fd, hdr, sizeof(hdr), timeout_ms) ||
        !SendAll(fd, body.data(), body.size(), timeout_ms) ||
        !SendAll(fd, tail, tail_len, timeout_ms) ||
        !RecvAll(fd, hdr, sizeof(hdr), timeout_ms)) {
      close(fd);
      if (pooled) continue;  // stale pooled socket: retry fresh
      return false;
    }
    int64_t len = GetInt64BE(hdr);
    *status = hdr[9];
    if (len < 0 || len > max_resp) {
      FDFS_LOG_WARN("dedup(sidecar): bogus response length %lld",
                    static_cast<long long>(len));
      close(fd);
      return false;
    }
    resp->resize(static_cast<size_t>(len));
    if (len > 0 && !RecvAll(fd, resp->data(), resp->size(), timeout_ms)) {
      close(fd);
      return false;
    }
    ReleaseFd(fd);
    return true;
  }
  return false;
}

DedupPlugin::Verdict SidecarDedup::Judge(const std::string& sha1_hex, int64_t) {
  Verdict v;
  std::string resp;
  uint8_t status = 0;
  if (!Rpc(static_cast<uint8_t>(StorageCmd::kDedupQuery), sha1_hex, &resp,
           &status)) {
    FDFS_LOG_WARN("dedup(sidecar): unreachable, treating as unique");
    return v;  // fail open
  }
  if (status == 0 && !resp.empty()) {
    v.duplicate = true;
    v.dup_of = resp;
  }
  return v;
}

void SidecarDedup::Notify(const std::string& body) {
  std::string resp;
  uint8_t status = 0;
  Rpc(static_cast<uint8_t>(StorageCmd::kDedupCommit), body, &resp, &status);
}

void SidecarDedup::Commit(const std::string& sha1_hex,
                          const std::string& file_id) {
  Notify("commitfile " + sha1_hex + " " + file_id);
}

void SidecarDedup::Forget(const std::string& file_id) {
  Notify("forget " + file_id);
}

// Sessions scope the sidecar's pending per-upload state.  The id embeds
// the daemon pid (multiple daemons may share one sidecar) and draws from
// one PROCESS-WIDE counter — the server holds two SidecarDedup instances
// (main loop + recovery thread), and per-instance counters would mint
// colliding ids for exactly the concurrent-upload case sessions exist
// to separate.
int64_t SidecarDedup::BeginChunked() {
  static std::atomic<int64_t> counter{0};
  return (static_cast<int64_t>(getpid()) << 32) |
         (counter.fetch_add(1, std::memory_order_relaxed) + 1);
}

// Fingerprint RPC (cmd 125): the daemon runs the native AVX2 CDC itself
// (identical gear table => identical cut points) and ships the cut
// offsets with the bytes — chunking is branchy scalar work the CPU does
// at GB/s, while the accelerator round-trip carries only the FLOP-heavy
// hash batches.  Request body: 8B BE session id + 8B BE base_offset +
// 8B BE n_cuts + n_cuts x 8B relative exclusive ends + raw segment.
// Response: 8B BE chunk_count then per chunk 8B offset + 8B length +
// 20B raw digest.
bool SidecarDedup::FingerprintChunks(int64_t session, const char* data,
                                     size_t len, int64_t base_offset,
                                     std::vector<ChunkFp>* out) {
  std::vector<int64_t> cuts = TimedGearChunkStream(data, len, widths_);
  // The segment is the request's tail: Rpc sends it from the caller's
  // buffer, so it is not copied here for the sake of one send().
  std::string body;
  body.reserve(24 + cuts.size() * 8);
  uint8_t num[8];
  PutInt64BE(session, num);
  body.append(reinterpret_cast<char*>(num), 8);
  PutInt64BE(base_offset, num);
  body.append(reinterpret_cast<char*>(num), 8);
  PutInt64BE(static_cast<int64_t>(cuts.size()), num);
  body.append(reinterpret_cast<char*>(num), 8);
  for (int64_t cut : cuts) {
    PutInt64BE(cut, num);
    body.append(reinterpret_cast<char*>(num), 8);
  }
  std::string resp;
  uint8_t status = 0;
  const int64_t fp_rpc_args[2] = {session, base_offset};
  if (!Rpc(static_cast<uint8_t>(StorageCmd::kDedupFingerprintCuts), body,
           &resp, &status, /*max_resp=*/256 << 20, data, len, fp_rpc_args) ||
      status != 0 || resp.size() < 8) {
    FDFS_LOG_WARN("dedup(sidecar): fingerprint unavailable, storing flat");
    return false;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(resp.data());
  int64_t count = GetInt64BE(p);
  if (count < 0 || resp.size() != 8 + static_cast<size_t>(count) * 36)
    return false;
  static const char* kHex = "0123456789abcdef";
  int64_t covered = 0;
  for (int64_t i = 0; i < count; ++i) {
    const uint8_t* rec = p + 8 + i * 36;
    ChunkFp fp;
    fp.offset = GetInt64BE(rec);
    fp.length = GetInt64BE(rec + 8);
    if (fp.length <= 0 || fp.offset != base_offset + covered) return false;
    fp.digest_hex.resize(40);
    for (int b = 0; b < 20; ++b) {
      fp.digest_hex[2 * b] = kHex[rec[16 + b] >> 4];
      fp.digest_hex[2 * b + 1] = kHex[rec[16 + b] & 0xF];
    }
    covered += fp.length;
    out->push_back(std::move(fp));
  }
  return covered == static_cast<int64_t>(len);
}

void SidecarDedup::CommitChunked(int64_t session, const std::string& file_id) {
  Notify("commitchunks " + std::to_string(session) + " " + file_id);
}

void SidecarDedup::AbortChunked(int64_t session) {
  Notify("abort " + std::to_string(session));
}

void SidecarDedup::ForgetChunked(const std::string& file_id) {
  Notify("forget " + file_id);
}

bool SidecarDedup::NearDups(const std::string& file_id, std::string* out,
                            bool* no_data) {
  std::string resp;
  uint8_t status = 0;
  if (!Rpc(static_cast<uint8_t>(StorageCmd::kDedupNeardups), file_id, &resp,
           &status))
    return false;  // sidecar down: same ENOTSUP surface as mode=cpu
  if (status == 61) {  // ENODATA: known mode, unindexed file
    *no_data = true;
    return true;
  }
  if (status != 0) return false;
  *out = std::move(resp);
  *no_data = false;
  return true;
}

bool SidecarDedup::VerifyChunks(const std::vector<ChunkFp>& chunks,
                                const std::string& payloads,
                                std::string* bad_mask) {
  if (chunks.empty()) {
    bad_mask->clear();
    return true;
  }
  // kDedupVerify body: 8B count + count x (8B length + 20B raw digest)
  // + the payloads concatenated; response = count bytes (0 ok / 1 bad).
  std::string body;
  uint8_t num[8];
  PutInt64BE(static_cast<int64_t>(chunks.size()), num);
  body.append(reinterpret_cast<char*>(num), 8);
  int64_t total = 0;
  for (const ChunkFp& c : chunks) {
    PutInt64BE(c.length, num);
    body.append(reinterpret_cast<char*>(num), 8);
    if (!HexToBytes(c.digest_hex, &body)) return false;
    total += c.length;
  }
  if (total != static_cast<int64_t>(payloads.size())) return false;
  body += payloads;
  std::string resp;
  uint8_t status = 0;
  if (!Rpc(static_cast<uint8_t>(StorageCmd::kDedupVerify), body, &resp,
           &status, static_cast<int64_t>(chunks.size()) + 1024) ||
      status != 0 || resp.size() != chunks.size())
    return false;  // sidecar down/old: caller verifies serially
  *bad_mask = std::move(resp);
  return true;
}

// kDedupEcEncode body: 8B k + 8B m + 8B shard_len + the k data shards,
// sent from the caller's buffer; response = the m parity shards.
bool SidecarDedup::EcEncode(int k, int m, int64_t shard_len,
                            const char* data, std::string* parity) {
  std::string body;
  uint8_t num[8];
  for (int64_t v : {static_cast<int64_t>(k), static_cast<int64_t>(m),
                    shard_len}) {
    PutInt64BE(v, num);
    body.append(reinterpret_cast<char*>(num), 8);
  }
  const int64_t want = static_cast<int64_t>(m) * shard_len;
  uint8_t status = 0;
  if (!Rpc(static_cast<uint8_t>(StorageCmd::kDedupEcEncode), body, parity,
           &status, want, data, static_cast<size_t>(k) * shard_len) ||
      status != 0 || static_cast<int64_t>(parity->size()) != want) {
    FDFS_LOG_WARN("dedup(sidecar): stripe parity unavailable (status %d)",
                  status);
    return false;
  }
  return true;
}

std::unique_ptr<DedupPlugin> MakeDedupPlugin(const std::string& mode,
                                             const std::string& base_path,
                                             const std::string& sidecar_path,
                                             int sidecar_idle_conns,
                                             CdcWidths widths) {
  if (mode == "cpu") {
    auto p = std::make_unique<CpuDedup>(base_path + "/data/dedup_index.dat",
                                        widths);
    p->LoadSnapshot();
    return p;
  }
  if (mode == "sidecar")
    return std::make_unique<SidecarDedup>(sidecar_path, sidecar_idle_conns,
                                          widths);
  return nullptr;  // none
}

}  // namespace fdfs
