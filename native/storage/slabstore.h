// Slab-packed record store: small chunks and recipe sidecars appended
// into large slab files, with an in-memory slot index and online
// compaction (ROADMAP item 1 — the billion-file scenario).
//
// Motivation: the content-addressed chunk store burns one inode + one
// open/rename per chunk digest and a second sidecar inode per recipe,
// so a corpus of millions of 4 KB files dies on filesystem metadata
// long before it dies on bytes (SURVEY §2.3 packs small LEGACY files
// into 64 MB trunk slabs for exactly this reason; storage/trunk.{h,cc}
// reproduces that for whole files — this store brings the same idea to
// the chunk/recipe layer every modern path uses).
//
// Disk layout: <store_path>/data/slabs/<10-digit-id>.slab — a pure
// sequence of CRC-framed records, appended to the highest-id ("active")
// slab until it reaches slab_bytes, then rolled to id+1.  Each record:
//
//   off  size  field
//   0    4     magic "FSLB"
//   4    1     version (1)
//   5    1     kind (1 = chunk payload, 2 = recipe sidecar)
//   6    1     flags (bit0 = dead)
//   7    1     key length
//   8    8     alloc length BE (payload bytes reserved; == payload today)
//   16   8     payload length BE
//   24   4     payload crc32 BE
//   28   8     mtime BE (unix seconds)
//   36   4     header crc32 BE (over bytes [0,36) with flags forced 0,
//              so MarkDead's one-byte flag flip never invalidates it)
//   40   ...   key bytes, then the payload
//
// Chunks are keyed by their 40-hex digest (content address); recipes by
// their sidecar path relative to the store root.  The slot index
// (key -> {slab id, offsets, length}) is RAM-only and sharded into 16
// stripes; it is rebuilt at boot by scanning every slab's headers —
// the same no-binlog-to-diverge philosophy as ChunkStore's
// RebuildFromRecipes and the trunk allocator's ScanRebuild.  A torn
// tail (crash mid-append) fails its magic/CRC and is truncated away; a
// duplicate key (crash between a compaction/replace append and the old
// record's dead mark) resolves newest-wins, the older record re-marked
// dead.
//
// Deletes mark slots dead: one flag byte flipped in place plus RAM
// byte-accounting — slab space is never reused in place.  The paced
// background compactor (driven from the scrub pass) copies the live
// records of the deadest slab into the active slab and unlinks it;
// crash-safe because every copy is re-appended (and indexed) before
// the source record dies.  Records that fail re-verify during the copy
// are left in place and reported upward, where ChunkStore routes them
// through the existing quarantine/heal machinery.
//
// Locking: SlabStore is self-locked and calls nothing that locks.  Its
// ranks sit BETWEEN the chunk-store stripes and the read cache
// (lockrank.h): ChunkStore calls in while holding a digest stripe lock
// (rank 90), and nothing here calls back out.  mu_ (kSlabStore, 92)
// guards the active-slab fd, rollover, and per-slab accounting; the 16
// index stripes (kSlabIndex, 94) guard the key map.  Reads are
// lock-free pread against a looked-up location, with one retry when a
// compaction unlinks the source slab between lookup and open (the
// record was re-appended before the source died, so the second lookup
// always lands on live bytes).
#pragma once

#include <array>
#include <atomic>
#include <cstdint>
#include <functional>
#include <map>
#include <string>
#include <unordered_map>
#include <vector>

#include "common/lockrank.h"

namespace fdfs {

constexpr uint8_t kSlabKindChunk = 1;
constexpr uint8_t kSlabKindRecipe = 2;
constexpr size_t kSlabRecordHeaderSize = 40;
constexpr size_t kSlabKeyMaxLen = 255;

// Fixed-size header + key, as parsed off disk (codec golden surface:
// fdfs_codec slab-layout pins the byte layout cross-language).
struct SlabRecordView {
  uint8_t kind = 0;
  uint8_t flags = 0;
  std::string key;
  int64_t alloc_len = 0;
  int64_t payload_len = 0;
  uint32_t payload_crc32 = 0;
  int64_t mtime = 0;
  int64_t record_len = 0;  // header + key + alloc
};

// Encode one record (header + key + payload).  mtime is stamped by the
// caller so tests and the codec golden are deterministic.
std::string SlabEncodeRecord(uint8_t kind, const std::string& key,
                             const char* data, size_t len, int64_t mtime);
// Parse the record starting at p (avail bytes readable).  False when
// the bytes do not form a valid record (bad magic/version/CRC, short
// buffer) — the boot scan treats that as the torn tail.
bool SlabDecodeRecord(const char* p, size_t avail, SlabRecordView* out);

class SlabStore {
 public:
  // dir: <store_path>/data/slabs (created on first append).
  // slab_bytes: roll the active slab past this size (>= 1 MB enforced
  // by config).  min_dead_pct: a slab becomes a compaction victim once
  // its dead bytes reach this share of its size.
  SlabStore(std::string dir, int64_t slab_bytes, int min_dead_pct);
  ~SlabStore();

  // One slot-index entry.  payload_off points at the payload bytes;
  // record_off at the record header (where the dead flag lives).
  // mtime mirrors the record header so orphan parking can age by it
  // without a disk read (crash-safe GC grace, like flat file mtime).
  struct Slot {
    int64_t slab_id = 0;
    int64_t record_off = 0;
    int64_t payload_off = 0;
    int64_t payload_len = 0;
    int64_t mtime = 0;
  };

  // Boot: scan every slab's record headers into the slot index,
  // truncating torn tails and resolving duplicate keys newest-wins.
  // Call once before serving (ChunkStore::RebuildFromRecipes drives it).
  void ScanRebuild();

  // Append one record and publish it in the slot index.  Replace
  // semantics: an existing record under the same key is marked dead
  // (never reused in place).  durable forces an fsync before the index
  // publish — recipe appends use it to keep WriteRecipeFile's
  // durability; chunk appends do not (flat chunk writes never synced).
  bool Append(uint8_t kind, const std::string& key, const char* data,
              size_t len, bool durable, std::string* err);

  bool Has(uint8_t kind, const std::string& key) const;
  bool Lookup(uint8_t kind, const std::string& key, Slot* slot) const;
  // Full / positional payload reads (pread; one retry through a fresh
  // lookup when a compaction unlinked the slab under us).
  bool Read(uint8_t kind, const std::string& key, std::string* out) const;
  bool ReadSlice(uint8_t kind, const std::string& key, int64_t offset,
                 int64_t len, char* dst) const;

  // One request of a vectored slice batch (ISSUE 18): [offset,
  // offset+len) of key's payload lands in dst.  The key pointer is
  // borrowed for the call.
  struct SliceRead {
    const std::string* key = nullptr;
    int64_t offset = 0;
    int64_t len = 0;
    char* dst = nullptr;
  };
  // Vectored positional reads for one response round: requests group by
  // slab file, sort by file offset, and offset-contiguous runs (small
  // inter-record gaps — header + key — bridged through a scrap buffer)
  // coalesce into ONE preadv each.  Per-request outcomes land in ok[n];
  // a request whose lookup or preadv raced a compaction simply reports
  // ok[i] = false here and retries through the per-request ReadSlice
  // path (same fresh-lookup semantics as Read).  *batches accumulates
  // preadv syscalls issued, *vec_spans the requests a successful preadv
  // served — the dio.preadv_* counter feed.
  void ReadSlices(uint8_t kind, const SliceRead* reqs, size_t n, bool* ok,
                  int64_t* batches, int64_t* vec_spans) const;

  // Delete: drop the index entry, flip the on-disk dead flag, account
  // the bytes.  False when the key is not indexed.  *payload_len_out
  // (optional) reports the payload size for reclaim accounting.
  bool MarkDead(uint8_t kind, const std::string& key,
                int64_t* payload_len_out = nullptr);

  // Iterate live records of one kind.  ForEachLive reads payloads
  // (recipe rebuild); ForEachLiveMeta is header-only (orphan scan).
  struct RecordMeta {
    std::string key;
    int64_t payload_len = 0;
    int64_t mtime = 0;
  };
  void ForEachLiveMeta(
      uint8_t kind, const std::function<void(const RecordMeta&)>& fn) const;
  void ForEachLive(uint8_t kind,
                   const std::function<void(const std::string& key,
                                            const std::string& payload)>& fn)
      const;

  // Online compaction: pick dead-enough slabs among those present when
  // the call began (the active one is retired first; slabs made during
  // the call wait for the next, so a call ends whatever the foreground
  // kills meanwhile), re-append their verified-live records, and unlink
  // them.  pace(n) is
  // called per record copied with the bytes read (the scrub manager's
  // token bucket slots in here); stop() is polled between records so
  // shutdown never waits on a long compaction.  Records whose payload
  // fails re-verify (chunk: SHA1 != key; recipe: crc32 mismatch) are
  // LEFT IN PLACE and returned in corrupt_chunk_keys /
  // corrupt_recipe_keys — the caller routes chunks through the
  // quarantine/heal machinery, which marks them dead and lets the next
  // pass finish the slab.
  struct CompactResult {
    int64_t slabs_compacted = 0;
    int64_t reclaimed_bytes = 0;  // slab file bytes unlinked
    int64_t copied_records = 0;
    std::vector<std::string> corrupt_chunk_keys;
    std::vector<std::string> corrupt_recipe_keys;
  };
  CompactResult Compact(const std::function<void(int64_t)>& pace,
                        const std::function<bool()>& stop);

  // Stats (slab.* registry gauges).  Byte counters account full record
  // extents (header + key + payload), i.e. what compaction can reclaim.
  // All atomics: gauge-fns run under the stats-registry mutex and must
  // never block on mu_ (held across pwrite/fsync — a stalled mount
  // would freeze every STAT/snapshot/SLO tick otherwise).
  int64_t files() const { return files_.load(); }
  int64_t slots_live() const { return slots_live_.load(); }
  int64_t slots_dead() const { return slots_dead_.load(); }
  int64_t bytes_live() const { return bytes_live_.load(); }
  int64_t bytes_dead() const { return bytes_dead_.load(); }
  int64_t compactions() const { return compactions_.load(); }
  int64_t compacted_bytes() const { return compacted_bytes_.load(); }

  const std::string& dir() const { return dir_; }

 private:
  static constexpr int kIndexStripes = 16;
  struct IndexStripe {
    mutable RankedMutex mu{LockRank::kSlabIndex};
    std::unordered_map<std::string, Slot> map;  // key: kind byte + key
  };
  struct SlabInfo {
    int64_t size_bytes = 0;
    int64_t live_slots = 0;
    int64_t dead_slots = 0;
    int64_t live_bytes = 0;  // record extents still indexed
    int64_t dead_bytes = 0;  // record extents marked dead
  };

  static std::string IndexKey(uint8_t kind, const std::string& key) {
    std::string k(1, static_cast<char>(kind));
    k += key;
    return k;
  }
  int StripeFor(const std::string& ikey) const;
  std::string SlabPath(int64_t slab_id) const;

  // mu_ held: ensure the active slab fd is open (rolling past
  // slab_bytes_), ready for an append of `need` bytes.
  bool EnsureActiveLocked(int64_t need, std::string* err);
  // Flip the on-disk dead flag for a record (best-effort: the RAM
  // accounting is authoritative until the next boot scan).
  void FlagDeadOnDisk(int64_t slab_id, int64_t record_off) const;
  // mu_ held: move one record's extent from live to dead accounting.
  void AccountDeadLocked(int64_t slab_id, int64_t record_extent);
  // Scan one slab file into the index (boot path).
  void ScanOneSlab(int64_t slab_id, const std::string& path,
                   std::vector<std::pair<std::string, Slot>>* dups);
  // Append while holding no locks on entry; used by both the public
  // Append and the compactor.  When `expect_old` is non-null the index
  // publish only replaces an entry still equal to *expect_old — if it
  // moved (concurrent delete / re-put), the freshly appended copy is
  // marked dead instead (compaction vs mutation race).
  bool AppendInternal(uint8_t kind, const std::string& key,
                      const char* data, size_t len, bool durable,
                      const Slot* expect_old, std::string* err);

  std::string dir_;
  int64_t slab_bytes_;
  int min_dead_pct_;

  // kSlabStore: active fd + rollover + per-slab accounting.  Appends
  // hold it across the file write, so all small writes serialize here
  // (a single buffered write — the price of one-active-slab append
  // layout, noted in OPERATIONS.md).
  mutable RankedMutex mu_{LockRank::kSlabStore};
  int active_fd_ = -1;
  int64_t active_id_ = 0;
  int64_t active_size_ = 0;
  std::map<int64_t, SlabInfo> slabs_;  // ordered: compaction picks low ids
  // Dead-flag write fd, cached per slab (mu_ held at every call site):
  // a mass delete or a compaction round flags thousands of records in
  // one slab — reopening the file per record would cost three syscalls
  // each.  Closed when the flagged slab changes, at unlink, and on
  // rescan.
  mutable int flag_fd_ = -1;
  mutable int64_t flag_fd_slab_ = 0;

  std::array<IndexStripe, kIndexStripes> index_;

  std::atomic<int64_t> files_{0};  // mirrors slabs_.size() (gauge-fn read)
  std::atomic<int64_t> slots_live_{0}, slots_dead_{0};
  std::atomic<int64_t> bytes_live_{0}, bytes_dead_{0};
  std::atomic<int64_t> compactions_{0}, compacted_bytes_{0};
};

}  // namespace fdfs
