// The daemon's chunked ingest: a stream through the dedup plugin into a
// chunk store, one segment at a time in the buffer the calling thread
// keeps.  Callers: the plain chunked upload (StoreChunked), the negotiated
// commit (AssembleCommit) and the re-index of a recovered file
// (ReindexFromRecipe); chunks that came from outside enter the store
// through AdmitChunk.  The server passes in what this needs; nothing here
// calls back into it.
#pragma once

#include <atomic>
#include <cstdint>
#include <functional>
#include <string>
#include <vector>

#include "storage/chunkstore.h"
#include "storage/dedup.h"

namespace fdfs {

struct StageTrace;  // common/trace.h

// The calling thread's kept buffer, room for `len` bytes; released (its
// pages lent back to the kernel) after a stream's last use.
char* KeptSegment(int64_t len);
void ReleaseKeptSegment();

// The one segment walk: [0, size) in `segment_bytes` segments, each given
// to fn(base, seg, len) in the kept buffer until fn answers false (so
// then does the walk).  The buffer is released after.
using SegmentFn = std::function<bool(int64_t base, char* seg, int64_t len)>;
bool WalkSegments(int64_t size, int64_t segment_bytes, const SegmentFn& fn);
// The same over a recipe's stream, each segment read from `cs` by one
// ReadChunkSlices (an entry across a segment end in two slices) before fn
// sees it.  False when a chunk is unreadable.
bool WalkRecipe(ChunkStore* cs, const Recipe& recipe, int64_t segment_bytes,
                const SegmentFn& fn);

// One fingerprint session: begun here (`reindex`: of bytes the node
// already stores, kDedupReindexSessionBit), committed by Commit, and
// aborted by the destructor otherwise, so no exit leaves it pending.
class FingerprintSession {
 public:
  FingerprintSession(DedupPlugin* plugin, bool reindex)
      : plugin_(plugin),
        id_(plugin->BeginChunked() | (reindex ? kDedupReindexSessionBit : 0)) {}
  ~FingerprintSession() {
    if (!committed_) plugin_->AbortChunked(id_);
  }
  FingerprintSession(const FingerprintSession&) = delete;
  FingerprintSession& operator=(const FingerprintSession&) = delete;
  bool Segment(const char* seg, int64_t len, int64_t base,
               std::vector<ChunkFp>* fps) {
    return plugin_->FingerprintChunks(id_, seg, static_cast<size_t>(len),
                                      base, fps);
  }
  void Commit(const std::string& file_ref) {
    plugin_->CommitChunked(id_, file_ref);
    committed_ = true;
  }

 private:
  DedupPlugin* const plugin_;
  const int64_t id_;
  bool committed_ = false;
};

// A chunk from outside (a client's commit, a replication peer, a recovery
// source): its bytes must BE its claimed digest before the store takes
// them, or every later dedup hit under it is poisoned.  kAdmitted: the
// store holds a reference and `e` is in *taken (the caller's rollback
// set); otherwise nothing was referenced (*err: the store's reason).
enum class Admission { kAdmitted, kNotItsDigest, kStoreError };
Admission AdmitChunk(ChunkStore* cs, const RecipeEntry& e, const char* data,
                     Recipe* taken, std::string* err);

// The STAT counters the ingest bumps (registry atomics, all or none):
// dedup.chunk_hits / chunk_misses, ec.write_stripes / write_bytes /
// encode_failures, ingest.commit_read_batches / commit_read_chunks.
struct IngestCounters {
  std::atomic<int64_t>* chunk_hits = nullptr;
  std::atomic<int64_t>* chunk_misses = nullptr;
  std::atomic<int64_t>* ec_write_stripes = nullptr;
  std::atomic<int64_t>* ec_write_bytes = nullptr;
  std::atomic<int64_t>* ec_encode_failures = nullptr;
  std::atomic<int64_t>* commit_read_batches = nullptr;
  std::atomic<int64_t>* commit_read_chunks = nullptr;
};

struct IngestTarget {
  ChunkStore* cs = nullptr;
  DedupPlugin* plugin = nullptr;
  int64_t segment_bytes = 0;
  bool ec_on_write = false;      // new chunks into RS stripes (cs with EC)
  StageTrace* stages = nullptr;  // the request's; null records nothing
  IngestCounters counters;
};

// The plain chunked upload of the tmp file: each segment read back,
// fingerprinted and its unseen chunks written, then the recipe and the
// session's commit to `file_ref`.  False (every reference given back) =>
// the caller stores the file flat.
bool StoreChunked(const IngestTarget& t, const std::string& tmp_path,
                  int64_t size, const std::string& rcp_path,
                  const std::string& file_ref, int64_t* saved_bytes,
                  int64_t* chunk_hits);

struct CommitAssembly {
  uint32_t crc = 0;             // of the logical stream
  int64_t saved = 0, hits = 0;  // present chunks' bytes and count
  int64_t missing = 0;          // shipped chunks
  bool fingerprinted = false;   // every segment went through the session
  Recipe taken;                 // the rollback set, in no order
};

// The negotiated commit's stream, segment by segment as this node cuts
// it: shipped entries (needed[i] != 0) read from `tmp_fd` and admitted,
// then present ones referenced and read by one ReadChunkSlices, the CRC
// folded and, with `fp`, the segment fingerprinted and held to the
// recipe.  Answers 0, 5 (EIO) or 22 (a foreign cut); on failure the
// caller gives out->taken back.
uint8_t AssembleCommit(const IngestTarget& t, const Recipe& recipe,
                       const std::string& needed, int tmp_fd,
                       int64_t session_id, FingerprintSession* fp,
                       CommitAssembly* out);

// A recovered file's bytes, read from its recipe, through a re-index
// session committed to `file_ref`.  Best-effort: a failure costs index
// coverage, never data.
void ReindexFromRecipe(const IngestTarget& t, const Recipe& recipe,
                       const std::string& file_ref);

}  // namespace fdfs
