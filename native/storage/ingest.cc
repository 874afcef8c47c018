#include "storage/ingest.h"

#include <fcntl.h>
#include <sys/mman.h>
#include <unistd.h>

#include <algorithm>
#include <memory>

#include "common/bytes.h"
#include "common/fsutil.h"
#include "common/log.h"
#include "common/trace.h"

namespace fdfs {

namespace {

struct SegmentBuf {
  std::unique_ptr<char[]> buf;
  int64_t cap = 0;
  int64_t written = 0;  // from the start, since the last ReleaseKeptSegment
};
thread_local SegmentBuf t_seg;

void Bump(std::atomic<int64_t>* c, int64_t n) {
  if (c != nullptr && n != 0) c->fetch_add(n, std::memory_order_relaxed);
}

// Whether the node's own cut of one segment (`fps`) is entries [first,
// first + n) of the client's recipe; if not, the client cut under other
// parameters (the WARN names the first entry that differs).
bool NodeCutsEqualRecipe(const std::vector<ChunkFp>& fps,
                         const Recipe& recipe, size_t first, size_t n,
                         int64_t session) {
  for (size_t k = 0; k < fps.size(); ++k) {
    if (k < n && recipe.chunks[first + k].length == fps[k].length &&
        recipe.chunks[first + k].digest_hex == fps[k].digest_hex)
      continue;
    FDFS_LOG_WARN("negotiated upload session %lld: at offset %lld this node "
                  "cuts %lld bytes %s, the client's recipe entry %zu differs",
                  static_cast<long long>(session),
                  static_cast<long long>(fps[k].offset),
                  static_cast<long long>(fps[k].length),
                  fps[k].digest_hex.c_str(), first + k);
    return false;
  }
  return fps.size() == n;
}

}  // namespace

// The buffer grows to the largest segment the thread has seen and is
// never zero-filled, so its pages are faulted in once a thread and not
// once an upload: a fresh 64 MB std::string a segment cost 2 ms per MB of
// upload, and mapping the tmp file 26 ms per MB more in the send to the
// sidecar (OPERATIONS.md, "Host memory").
char* KeptSegment(int64_t len) {
  SegmentBuf& s = t_seg;
  if (s.cap < len) {
    s.cap = 0;  // a throwing new leaves no capacity behind a null buffer
    s.written = 0;
    s.buf.reset();  // the old one first: never both resident
    s.buf.reset(new char[static_cast<size_t>(len)]);
    s.cap = len;
  }
  s.written = std::max(s.written, len);
  return s.buf.get();
}

// The buffer's bytes are dead: the kernel may take its pages when it
// needs memory, without swapping them; until then the next stream writes
// over them without a fault.  What idle workers hold is a loan and not a
// reservation (OPERATIONS.md, "Host memory").
void ReleaseKeptSegment() {
#ifdef MADV_FREE
  SegmentBuf& s = t_seg;
  const uintptr_t page = static_cast<uintptr_t>(sysconf(_SC_PAGESIZE));
  uintptr_t lo = reinterpret_cast<uintptr_t>(s.buf.get());
  uintptr_t hi = (lo + static_cast<uintptr_t>(s.written)) & ~(page - 1);
  lo = (lo + page - 1) & ~(page - 1);
  if (hi > lo) madvise(reinterpret_cast<void*>(lo), hi - lo, MADV_FREE);
  s.written = 0;
#endif
}

bool WalkSegments(int64_t size, int64_t segment_bytes, const SegmentFn& fn) {
  bool ok = true;
  for (int64_t base = 0; ok && base < size; base += segment_bytes) {
    const int64_t len = std::min(segment_bytes, size - base);
    ok = fn(base, KeptSegment(len), len);
  }
  ReleaseKeptSegment();
  return ok;
}

bool WalkRecipe(ChunkStore* cs, const Recipe& recipe, int64_t segment_bytes,
                const SegmentFn& fn) {
  size_t i = 0;             // the first entry not wholly read yet
  int64_t entry_base = 0;   // its offset in the stream
  int64_t batches = 0, spans = 0;
  std::vector<ChunkStore::SliceReq> reads;
  return WalkSegments(
      recipe.logical_size, segment_bytes,
      [&](int64_t base, char* seg, int64_t len) {
        reads.clear();
        int64_t filled = 0;
        while (i < recipe.chunks.size() && entry_base < base + len) {
          const RecipeEntry& e = recipe.chunks[i];
          const int64_t lo = std::max(base, entry_base);
          const int64_t hi = std::min(base + len, entry_base + e.length);
          reads.push_back({&e.digest_hex, lo - entry_base, hi - lo,
                           seg + (lo - base)});
          filled += hi - lo;
          if (hi < entry_base + e.length) break;  // goes on past this segment
          entry_base += e.length;
          ++i;
        }
        std::string failed;
        return filled == len &&
               cs->ReadChunkSlices(reads.data(), reads.size(), &batches,
                                   &spans, &failed) &&
               fn(base, seg, len);
      });
}

Admission AdmitChunk(ChunkStore* cs, const RecipeEntry& e, const char* data,
                     Recipe* taken, std::string* err) {
  const size_t len = static_cast<size_t>(e.length);
  if (Sha1(data, len).Hex() != e.digest_hex) return Admission::kNotItsDigest;
  bool existed = false;
  if (!cs->PutAndRef(e.digest_hex, data, len, &existed, err))
    return Admission::kStoreError;
  taken->chunks.push_back(e);
  return Admission::kAdmitted;
}

// Each segment's read-back, fingerprint and chunk-store writes are
// intervals of t.stages, one per segment and stage.
bool StoreChunked(const IngestTarget& t, const std::string& tmp_path,
                  int64_t size, const std::string& rcp_path,
                  const std::string& file_ref, int64_t* saved_bytes,
                  int64_t* chunk_hits) {
  ChunkStore* const cs = t.cs;
  const IngestCounters& ctr = t.counters;
  int fd = open(tmp_path.c_str(), O_RDONLY);
  if (fd < 0) return false;
  FingerprintSession session(t.plugin, /*reindex=*/false);
  ChunkStore::ParityFn encode;  // null: the chunk store's host codec
  if (t.plugin->ComputesParity())
    encode = [plugin = t.plugin](int k, int m, int64_t shard_len,
                                 const char* data, std::string* parity) {
      return plugin->EcEncode(k, m, shard_len, data, parity);
    };
  Recipe recipe;
  recipe.logical_size = size;
  bool ok = WalkSegments(size, t.segment_bytes, [&](int64_t seg_base,
                                                    char* seg, int64_t want) {
    StageScope readback(t.stages, Stage::kReadback);
    if (!PreadFull(fd, seg, want, seg_base)) return false;
    readback.End();
    // The chunker, the lock wait and the RPC nest under it (dedup.cc);
    // then only unseen chunks are written.
    std::vector<ChunkFp> fps;
    StageScope fingerprint(t.stages, Stage::kFingerprint);
    if (!session.Segment(seg, want, seg_base, &fps))
      return false;  // fingerprinting unavailable: caller stores flat
    fingerprint.End();
    StageScope cs_write(t.stages, Stage::kCsWrite);
    ChunkStore::StripeWrite w;
    std::string err;
    bool put = true;
    if (t.ec_on_write && cs->ec_enabled()) {
      // The segment's new chunks into one stripe.  A stripe the plugin
      // does not encode fails the chunked store, and the upload takes
      // the flat fallback an unavailable fingerprint takes.
      StageScope ec_write(t.stages, Stage::kEcWrite);
      std::vector<ChunkStore::SegmentChunk> seg_chunks;
      seg_chunks.reserve(fps.size());
      for (const ChunkFp& fp : fps)
        seg_chunks.push_back(
            {&fp.digest_hex, seg + (fp.offset - seg_base), fp.length});
      put = cs->PutSegmentStriped(seg_chunks, encode, &recipe, &w, &err);
    } else {
      for (const ChunkFp& fp : fps) {
        bool existed = false;
        put = cs->PutAndRef(fp.digest_hex, seg + (fp.offset - seg_base),
                            fp.length, &existed, &err);
        if (!put) break;
        ++(existed ? w.chunk_hits : w.chunk_misses);
        if (existed) w.saved_bytes += fp.length;
        recipe.chunks.push_back({fp.digest_hex, fp.length});
      }
    }
    if (!put) FDFS_LOG_ERROR("chunk store: %s", err.c_str());
    *saved_bytes += w.saved_bytes;
    *chunk_hits += w.chunk_hits;
    Bump(ctr.chunk_hits, w.chunk_hits);
    Bump(ctr.chunk_misses, w.chunk_misses);
    Bump(ctr.ec_encode_failures, w.encode_failed ? 1 : 0);
    if (w.stripe_bytes > 0) {
      Bump(ctr.ec_write_stripes, 1);
      Bump(ctr.ec_write_bytes, w.stripe_bytes);
    }
    return put;
  });
  close(fd);
  std::string err;
  // The recipe's write is a chunk-store write like the chunks' (an
  // upload's recipe_us column stays the negotiated commit's alone).
  StageScope recipe_write(ok ? t.stages : nullptr, Stage::kCsWrite);
  if (!ok || !cs->StoreRecipe(rcp_path, recipe, &err)) {
    if (ok) FDFS_LOG_ERROR("recipe write: %s", err.c_str());
    // Roll back references taken so far; untouched chunks stay for
    // other recipes, newly-written orphans fall to the startup GC.
    cs->UnrefAll(recipe);
    return false;
  }
  recipe_write.End();
  session.Commit(file_ref);
  return true;
}

uint8_t AssembleCommit(const IngestTarget& t, const Recipe& recipe,
                       const std::string& needed, int tmp_fd,
                       int64_t session_id, FingerprintSession* fp,
                       CommitAssembly* out) {
  ChunkStore* const cs = t.cs;
  out->fingerprinted = fp != nullptr;
  uint8_t status = 0;  // the commit's answer so far: 0, EIO or EINVAL
  std::vector<ChunkStore::SliceReq> reads;
  size_t first = 0;     // the segment's first recipe entry
  int64_t tmp_off = 0;  // next shipped chunk's offset in the tmp file
  int64_t read_batches = 0, read_chunks = 0;  // ReadChunkSlices' preadvs
  WalkSegments(recipe.logical_size, t.segment_bytes, [&](int64_t base,
                                                         char* seg,
                                                         int64_t seg_len) {
    // This node cuts every segment on its own: an entry across a segment
    // end is a foreign cut, and has no place in the buffer either.
    size_t end = first;
    int64_t fill = 0;
    while (end < recipe.chunks.size() &&
           fill + recipe.chunks[end].length <= seg_len)
      fill += recipe.chunks[end++].length;
    if (fill != seg_len) {
      FDFS_LOG_WARN("negotiated upload session %lld: the client's recipe "
                    "entry %zu crosses offset %lld, where this node's cut "
                    "of a %lld-byte segment ends",
                    static_cast<long long>(session_id), end,
                    static_cast<long long>(base + seg_len),
                    static_cast<long long>(t.segment_bytes));
      status = 22;
      return false;
    }
    // This segment's chunk-store share: verify, then present, inside it.
    StageScope cs_write(t.stages, Stage::kCsWrite);
    StageScope verify(t.stages, Stage::kVerify);
    // Shipped entries first, so that a digest this commit ships is in
    // the store before a later occurrence of it is read from there.
    int64_t at = 0;  // the entry's place in the segment
    for (size_t i = first; i < end; at += recipe.chunks[i].length, ++i) {
      if (needed[i] == 0) continue;
      const RecipeEntry& e = recipe.chunks[i];
      ++out->missing;
      std::string err;
      const Admission a = PreadFull(tmp_fd, seg + at, e.length, tmp_off)
                              ? AdmitChunk(cs, e, seg + at, &out->taken, &err)
                              : Admission::kNotItsDigest;  // cut short
      if (a == Admission::kNotItsDigest)
        FDFS_LOG_WARN("negotiated upload: chunk %s failed digest check",
                      e.digest_hex.c_str());
      else if (a == Admission::kStoreError)
        FDFS_LOG_ERROR("negotiated upload chunk store: %s", err.c_str());
      if (a != Admission::kAdmitted) {
        status = 5;
        break;
      }
      tmp_off += e.length;
    }
    verify.End();
    StageScope present(t.stages, Stage::kPresent);
    const int64_t batches0 = read_batches, chunks0 = read_chunks;
    reads.clear();
    at = 0;
    for (size_t i = first; status == 0 && i < end;
         at += recipe.chunks[i].length, ++i) {
      if (needed[i] != 0) continue;
      const RecipeEntry& e = recipe.chunks[i];
      int64_t stored_len = -1;
      if (!cs->RefOne(e.digest_hex, &stored_len)) {
        // Deleted since the bitmap (the pin defers the unlink only): the
        // client re-sends the whole payload.
        FDFS_LOG_WARN("negotiated upload: chunk %s vanished before commit",
                      e.digest_hex.c_str());
        status = 5;
        break;
      }
      out->taken.chunks.push_back(e);
      // The batched read checks bounds only: a stored chunk of another
      // length than the recipe's is not the recipe's chunk.
      if (stored_len != e.length) {
        FDFS_LOG_WARN("negotiated upload: chunk %s is %lld bytes in the "
                      "store, %lld in the recipe", e.digest_hex.c_str(),
                      static_cast<long long>(stored_len),
                      static_cast<long long>(e.length));
        status = 5;
        break;
      }
      reads.push_back({&e.digest_hex, 0, e.length, seg + at});
      out->saved += e.length;
      ++out->hits;
    }
    std::string unreadable;
    if (status == 0 && !reads.empty() &&
        !cs->ReadChunkSlices(reads.data(), reads.size(), &read_batches,
                             &read_chunks, &unreadable)) {
      FDFS_LOG_WARN("negotiated upload: chunk %s unreadable at commit",
                    unreadable.c_str());
      status = 5;
    }
    if (status != 0) return false;
    out->crc = Crc32(seg, static_cast<size_t>(seg_len), out->crc);
    present.SetArgs(read_chunks - chunks0, read_batches - batches0);
    present.End();
    cs_write.End();
    if (out->fingerprinted) {
      // The chunker, the lock wait and the RPC nest under it (dedup.cc).
      StageScope reindex(t.stages, Stage::kReindex);
      std::vector<ChunkFp> fps;
      out->fingerprinted = fp->Segment(seg, seg_len, base, &fps);
      if (out->fingerprinted &&
          !NodeCutsEqualRecipe(fps, recipe, first, end - first, session_id))
        status = 22;
    }
    first = end;
    return status == 0;
  });
  Bump(t.counters.commit_read_batches, read_batches);
  Bump(t.counters.commit_read_chunks, read_chunks);
  return status;
}

// Without it a sidecar-mode rebuild would leave every recovered file
// invisible to NEAR_DUPS and un-forgettable on delete.
void ReindexFromRecipe(const IngestTarget& t, const Recipe& recipe,
                       const std::string& file_ref) {
  FingerprintSession session(t.plugin, /*reindex=*/true);
  if (WalkRecipe(t.cs, recipe, t.segment_bytes,
                 [&](int64_t base, char* seg, int64_t len) {
                   std::vector<ChunkFp> fps;
                   return session.Segment(seg, len, base, &fps);
                 }))
    session.Commit(file_ref);
}

}  // namespace fdfs
