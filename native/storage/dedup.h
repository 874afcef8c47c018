// Dedup plugin boundary on the upload path.
//
// This is the rebuild's analogue of the reference's storage-plugin hook in
// storage/storage_func.h (north star: "gated behind the existing
// storage-plugin hook so the classic C path remains the default").  Two
// granularities:
//
//  * Whole-file (Judge/Commit/Forget): files below the chunking threshold
//    are judged by their stream SHA1; duplicates become hardlinks + an 'L'
//    binlog record.
//  * Chunk-level (FingerprintChunks): larger streams are content-defined
//    chunked and per-chunk fingerprinted; the daemon then writes only
//    chunks its ChunkStore has never seen and a small recipe file.  The
//    fingerprinting is the accelerated part — the sidecar runs CDC +
//    batched SHA1 + MinHash on the TPU (fastdfs_tpu/sidecar.py); the cpu
//    plugin is the serial C++ referee with identical cut-points.
//
// Modes: none (classic CRC32-only path), cpu (in-process), sidecar (TPU
// engine over a unix socket).  The sidecar path FAILS OPEN: uploads never
// block on the accelerator — unreachable sidecar means store-flat.
#pragma once

#include <cstdint>
#include <memory>
#include <mutex>

#include "common/cdc.h"  // CdcWidths
#include "common/lockrank.h"
#include <string>
#include <unordered_map>
#include <vector>

namespace fdfs {

struct StageTrace;  // common/trace.h: a request's stage intervals

struct ChunkFp {
  int64_t offset = 0;
  int64_t length = 0;
  std::string digest_hex;  // 40-char lowercase SHA1 of the chunk bytes
};

// A session opened to re-index bytes the daemon already stores (a
// negotiated upload's commit, a recovered file) carries this bit in its
// id (sidecar.py, REINDEX_SESSION_BIT): the sidecar's spans and stats
// tell such requests from an upload's.  BeginChunked never sets it.
constexpr int64_t kDedupReindexSessionBit = int64_t{1} << 62;

class DedupPlugin {
 public:
  virtual ~DedupPlugin() = default;

  struct Verdict {
    bool duplicate = false;
    std::string dup_of;  // existing file id (full "group/M.." form)
  };

  // -- whole-file granularity --------------------------------------------
  virtual Verdict Judge(const std::string& sha1_hex, int64_t file_size) = 0;
  virtual void Commit(const std::string& sha1_hex, const std::string& file_id) = 0;
  virtual void Forget(const std::string& file_id) = 0;  // on delete
  virtual bool Save() { return true; }   // snapshot (checkpoint/resume)
  virtual const char* Name() const = 0;

  // What the plugin is: an instance of its own for another thread
  // (recovery, scrub), or null to share this one; whether it keeps an
  // index beside the chunk store (near-dup signatures, attributions) that
  // bytes stored without its fingerprint (a negotiated commit, a recovered
  // recipe) must be re-indexed into; whether EcEncode computes parity.
  virtual std::unique_ptr<DedupPlugin> ForAnotherThread() const {
    return nullptr;
  }
  virtual bool IndexesBesideChunkStore() const { return false; }
  virtual bool ComputesParity() const { return false; }

  // -- chunk granularity -------------------------------------------------
  // One chunked upload = one SESSION: BeginChunked() mints an id that
  // scopes all pending fingerprint state (file signature, digest
  // attributions) until CommitChunked binds it to the final file id or
  // AbortChunked discards it (flat-fallback, failed upload).  Explicit
  // sessions — not connection identity — so concurrent uploads over one
  // plugin and multi-threaded daemons (work_threads > 1) cannot
  // interleave state.
  virtual int64_t BeginChunked() { return 0; }
  // CDC + per-chunk SHA1 over one SEGMENT of an upload stream.  Segments
  // are independently chunked (CDC restarts at segment boundaries) so a
  // multi-GB file never needs a contiguous buffer; `base_offset` shifts
  // the reported chunk offsets to absolute stream positions.  Returns
  // false when chunk fingerprinting is unavailable (caller stores flat).
  virtual bool FingerprintChunks(int64_t session, const char* data,
                                 size_t len, int64_t base_offset,
                                 std::vector<ChunkFp>* out) {
    (void)session; (void)data; (void)len; (void)base_offset; (void)out;
    return false;
  }
  // Chunked-file lifecycle notifications (near-dup index bookkeeping in
  // the sidecar; no-ops for the cpu plugin — its ChunkStore IS the index).
  virtual void CommitChunked(int64_t session, const std::string& file_id) {
    (void)session; (void)file_id;
  }
  virtual void AbortChunked(int64_t session) { (void)session; }
  virtual void ForgetChunked(const std::string& file_id) { (void)file_id; }

  // Ranked near-dup report for a stored file (kNearDups command): *out
  // gets text lines "<file_id> <score>".  Returns false when this mode
  // has no near index (none/cpu — the caller answers ENOTSUP);
  // *no_data=true when the mode supports it but the file carries no
  // signature (ENODATA).
  virtual bool NearDups(const std::string& file_id, std::string* out,
                        bool* no_data) {
    (void)file_id; (void)out; (void)no_data;
    return false;
  }

  // Batched chunk-integrity verify for the scrubber (kDedupVerify RPC):
  // `payloads` is each chunk's bytes concatenated in `chunks` order
  // (lengths from ChunkFp::length; digests from digest_hex).  On
  // success *bad_mask has one byte per chunk (0 = digest matches,
  // 1 = mismatch).  Returns false when batched verification is
  // unavailable (none/cpu modes, sidecar unreachable) — the caller
  // falls back to its serial host SHA1.
  virtual bool VerifyChunks(const std::vector<ChunkFp>& chunks,
                            const std::string& payloads,
                            std::string* bad_mask) {
    (void)chunks; (void)payloads; (void)bad_mask;
    return false;
  }

  // The m parity shards of one RS(k, m) stripe (kDedupEcEncode RPC; the
  // erasure coding on write): `data` is the k data shards, shard_len
  // bytes each, end to end; *parity gets m x shard_len bytes.  Returns
  // false when this mode computes no parity (none/cpu: the chunk store's
  // host RsEncode is the codec there) or the sidecar could not (the
  // segment is then not striped: ChunkStore::PutSegmentStriped).
  virtual bool EcEncode(int k, int m, int64_t shard_len, const char* data,
                        std::string* parity) {
    (void)k; (void)m; (void)shard_len; (void)data; (void)parity;
    return false;
  }
};

// CPU baseline: exact SHA1 digest map, snapshotted to
// <base_path>/data/dedup_index.dat (atomic write-then-rename); chunk
// fingerprints via the serial gear CDC (common/cdc.h).
class CpuDedup : public DedupPlugin {
 public:
  explicit CpuDedup(std::string snapshot_path, CdcWidths widths = {});
  Verdict Judge(const std::string& sha1_hex, int64_t file_size) override;
  void Commit(const std::string& sha1_hex, const std::string& file_id) override;
  void Forget(const std::string& file_id) override;
  bool Save() override;
  const char* Name() const override { return "cpu"; }
  bool FingerprintChunks(int64_t session, const char* data, size_t len,
                         int64_t base_offset,
                         std::vector<ChunkFp>* out) override;
  bool LoadSnapshot();
  size_t size() const { return by_digest_.size(); }

 private:
  std::string snapshot_path_;
  const CdcWidths widths_;
  mutable RankedMutex mu_{LockRank::kDedupEngine};  // handlers run on every nio/dio thread
  std::unordered_map<std::string, std::string> by_digest_;  // sha1 -> file id
  std::unordered_map<std::string, std::string> by_file_;    // file id -> sha1
};

// Sidecar: TPU dedup engine process over a unix-domain socket, speaking
// the DEDUP_* opcodes on the standard framing (see
// fastdfs_tpu/sidecar.py).  Falls open (treats everything as unique /
// unchunkable) when the sidecar is unreachable.  Every connection opens
// with "widths <min> <avg_bits> <max>" (a DEDUP_COMMIT): a sidecar whose
// engine runs other chunk widths answers an error, the connection is
// dropped with an ERROR line, and nothing is fingerprinted over it, so no
// recipe is ever stored under mixed widths.
class SidecarDedup : public DedupPlugin {
 public:
  // max_idle_fds: how many idle connections the pool keeps; the daemon
  // passes its dio workers' total, so that no worker's connection is
  // closed behind it (kMinIdleFds at least: nio threads, the scrubber
  // and recovery make RPCs too).
  SidecarDedup(std::string socket_path, int max_idle_fds,
               CdcWidths widths = {});
  ~SidecarDedup() override;
  Verdict Judge(const std::string& sha1_hex, int64_t file_size) override;
  void Commit(const std::string& sha1_hex, const std::string& file_id) override;
  void Forget(const std::string& file_id) override;
  const char* Name() const override { return "sidecar"; }
  std::unique_ptr<DedupPlugin> ForAnotherThread() const override {
    return std::make_unique<SidecarDedup>(socket_path_, 0, widths_);
  }
  bool IndexesBesideChunkStore() const override { return true; }
  bool ComputesParity() const override { return true; }
  int64_t BeginChunked() override;
  bool FingerprintChunks(int64_t session, const char* data, size_t len,
                         int64_t base_offset,
                         std::vector<ChunkFp>* out) override;
  void CommitChunked(int64_t session, const std::string& file_id) override;
  void AbortChunked(int64_t session) override;
  void ForgetChunked(const std::string& file_id) override;
  bool NearDups(const std::string& file_id, std::string* out,
                bool* no_data) override;
  bool VerifyChunks(const std::vector<ChunkFp>& chunks,
                    const std::string& payloads,
                    std::string* bad_mask) override;
  bool EcEncode(int k, int m, int64_t shard_len, const char* data,
                std::string* parity) override;

 private:
  // Connection pool: each in-flight RPC borrows its own fd, so
  // concurrent dio threads overlap their sidecar round-trips instead of
  // serializing on one shared connection (the sidecar itself only
  // serializes index mutation, not fingerprint compute).  Up to
  // max_idle_fds_ idle connections are retained.
  static constexpr int kMinIdleFds = 4;
  // *pooled reports whether the fd came from the idle pool (a failure
  // on it retries once on a fresh connection — pooled sockets go stale
  // when the sidecar restarts).  -1 on connect failure.
  // The wait for the pool's mutex is an interval (storage.fp_lock) of
  // `stages`, a fingerprint RPC's request; null records nothing.
  int AcquireFd(bool* pooled, StageTrace* stages);
  void ReleaseFd(int fd);   // return a healthy fd to the pool
  // The request's bytes are `body` then `tail` (a fingerprint segment,
  // sent from the caller's buffer).  fp_rpc_args: {session, base_offset}
  // of a fingerprint RPC, which is then recorded as storage.fp_rpc.
  bool Rpc(uint8_t cmd, const std::string& body, std::string* resp,
           uint8_t* status, int64_t max_resp = 1 << 20,
           const char* tail = nullptr, size_t tail_len = 0,
           const int64_t* fp_rpc_args = nullptr);
  // The first exchange on a fresh connection; false = refused or dead.
  bool Handshake(int fd);
  // A DEDUP_COMMIT whose answer nothing waits on (commit, abort, forget).
  void Notify(const std::string& body);
  std::string socket_path_;
  const int max_idle_fds_;
  const CdcWidths widths_;
  RankedMutex mu_{LockRank::kDedupPool};  // guards pool_
  std::vector<int> pool_;
};

// sidecar_idle_conns: see SidecarDedup's constructor (other modes
// ignore it).  widths: storage.conf's dedup_cdc_widths; both plugins cut
// with them.
std::unique_ptr<DedupPlugin> MakeDedupPlugin(const std::string& mode,
                                             const std::string& base_path,
                                             const std::string& sidecar_path,
                                             int sidecar_idle_conns = 0,
                                             CdcWidths widths = {});

}  // namespace fdfs
