// Content-addressed chunk store + file recipes: the disk layer of
// chunk-level dedup.
//
// North star (BASELINE.json): the upload path chunks each stream
// (CDC), fingerprints the chunks (SHA1 — on TPU in sidecar mode), and
// writes only bytes the store has never seen.  This class owns the
// physical side:
//
//   <store_path>/data/chunks/<d0d1>/<d2d3>/<40-hex>   chunk payloads
//   <local path>.rcp                                  per-file recipes
//
// A recipe lists (digest, length) per chunk; logical reads reassemble.
// The store is self-healing: Put() is write-if-absent keyed by content
// digest, so a stale "duplicate" verdict can never lose data — the byte
// payload is always provided alongside the digest.
//
// Refcounts are RAM-only and rebuilt by scanning every recipe at startup
// (which doubles as orphan-chunk GC); crash-safety therefore never
// depends on a refcount file.
//
// Locking (the PR 5 read-path overhaul): the per-digest state (refs,
// lengths, pins, zero-ref parking, quarantine marks) is SHARDED into
// kStripes lock stripes keyed by the digest's first hex nibble, so
// concurrent downloads, uploads, deletes, and the scrub pass stop
// convoying on one mutex.  Every invariant from the integrity engine
// era is PER-DIGEST (probe+pin in one acquisition, pin-vs-GC-unlink in
// one acquisition, quarantine re-verify under the same lock as the
// rename), so a single stripe lock preserves each of them; the only
// cross-digest atomicity anywhere is RefAll's all-or-nothing check,
// which takes its (few) stripes in ascending index order — the
// deadlock-free ordered multi-stripe protocol.  ReadRecipeAndPin keeps
// its fail-before-first-byte contract by verify+pin per chunk with
// rollback: a delete interleaving mid-recipe makes the pin step find
// the unref'd chunk and the whole download fails cleanly with no pins
// held, exactly as the monolithic lock produced.  Aggregate byte/count
// accounting is atomics.  This class is self-locked and calls nothing
// that locks (the read cache has its own mutex, always acquired AFTER
// a stripe lock, never before).
//
// Hot-chunk read cache: a bounded LRU of whole chunk payloads
// (storage.conf:read_cache_mb; 0 = off) consulted by the download and
// FETCH_CHUNK serving paths.  Entries are shared_ptr<const string>, so
// an eviction or invalidation never frees bytes a response is still
// scattering into the socket.  Strict coherence with mutation: inserts
// re-check refs+quarantine UNDER the digest's stripe lock, and
// Quarantine(), RepairChunk(), and the GC/delete unlink invalidate
// under that same lock — a quarantined or swept chunk can never be
// served from the cache afterward.  Slab-resident chunks key the cache
// identically to flat ones (by digest), so the same invalidation
// points cover both layouts.
//
// Slab packing (ISSUE 9 / ROADMAP item 1): chunks below
// slab_chunk_threshold and recipe payloads below slab_recipe_threshold
// live as records inside <store_path>/data/slabs/*.slab
// (storage/slabstore.h) instead of per-object inodes.  Every
// per-digest invariant is unchanged — the slab store is a payload
// landing zone consulted under the SAME stripe-lock acquisitions that
// previously wrote/unlinked flat files (slab lock ranks sit between
// kChunkStripe and kReadCache).  Recipes load/store through
// StoreRecipe/LoadRecipe, which route small ones into the slab keyed
// by their sidecar path relative to the store root (mixed stores read
// both layouts, so flipping the thresholds is always safe).
//
// Erasure-coded cold tier (ISSUE 16 / ROADMAP item 2): when ec_k > 0
// the store owns an EcStore (<store_path>/data/ec/, storage/ecstore.h)
// and three new per-digest states exist.  EC-RESIDENT (owner): the
// payload was demoted into an RS(k, m) stripe and the local flat/slab
// copy dropped — refs/lens are unchanged and reads fall through
// flat -> slab -> EC transparently.  RELEASED (peer): scrub stage 5's
// verify-then-release handover (EC_RELEASE) dropped this node's replica
// because the group owner holds the bytes in parity — refs/lens are
// unchanged, presence answers (HaveMask/PinAndMask) still report the
// chunk held (it is, group-wide), and a local read remote-fetches from
// the owner via the set_remote_fetch hook (SHA1-verified, cache-
// warmed).  Released marks survive restarts via data/released.log
// ("R <digest> <len>" / "H <digest>" records, replayed by
// RebuildFromRecipes); heal paths (PutAndRef, RepairChunk) clear the
// mark the moment verified bytes land locally again.  Deletes reclaim
// parity through EcStore::MarkDead from the same stripe-lock unlink
// path that reclaims flat/slab bytes.
//
// Reference anchor: replaces the inode-per-file write in
// storage/storage_dio.c:dio_write_file() for deduplicated uploads.
#pragma once

#include <array>

#include "common/lockrank.h"
#include <atomic>
#include <cstdint>
#include <functional>
#include <list>
#include <memory>
#include <mutex>
#include <optional>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "storage/ecstore.h"
#include "storage/slabstore.h"

namespace fdfs {

struct RecipeEntry {
  std::string digest_hex;  // 40-char lowercase SHA1
  int64_t length = 0;
};

struct Recipe {
  int64_t logical_size = 0;
  std::vector<RecipeEntry> chunks;
};

// Recipe codec ("FDFSRCP1" magic + BE fields; see chunkstore.cc).  The
// buffer forms are the shared core: recipe files and slab-resident
// recipe records carry identical bytes.
std::string EncodeRecipe(const Recipe& r);
std::optional<Recipe> DecodeRecipe(const char* data, size_t len);
bool WriteRecipeFile(const std::string& path, const Recipe& r,
                     std::string* err);
std::optional<Recipe> ReadRecipeFile(const std::string& path);

// Slab-packing knobs (storage.conf slab_* keys; see slabstore.h).
// Thresholds of 0 disable packing for that record class; both 0 = no
// slab store at all (the pre-slab flat layout).
struct SlabOptions {
  int64_t chunk_threshold = 0;   // chunks below this pack into slabs
  int64_t recipe_threshold = 0;  // encoded recipes below this pack too
  int64_t slab_bytes = 64LL << 20;
  int compact_min_dead_pct = 25;
};

class ChunkStore {
 public:
  // gc_grace_s: how long a zero-ref chunk's bytes linger on disk before
  // a GcSweep may reclaim them (0 = unlink eagerly on the last unref,
  // the pre-scrubber behavior).  read_cache_bytes bounds the hot-chunk
  // LRU read cache (0 = off).  ec_k/ec_m enable the erasure-coded cold
  // tier (storage.conf ec_k/ec_m; 0 = off — like the slab store, an
  // EcStore also mounts read-only when data/ec/ already holds stripes,
  // so flipping ec_k to 0 drains the tier instead of stranding it).
  explicit ChunkStore(std::string store_path, int64_t gc_grace_s = 0,
                      int64_t read_cache_bytes = 0,
                      SlabOptions slab = SlabOptions{}, int ec_k = 0,
                      int ec_m = 0);

  // Flight recorder (common/eventlog.h; may stay null): the store
  // reports heal-on-upload — a quarantined chunk restored by an
  // incoming verified payload — so postmortems see the full
  // quarantine -> heal lifecycle, not just the scrubber's half.  Set
  // once at startup, before serving.
  void set_events(class EventLog* events) { events_ = events; }

  // Scan every *.rcp under the data dir: rebuild refcounts and delete
  // orphaned chunk files.  Call once at startup, before serving.
  void RebuildFromRecipes();

  // Write-if-absent + take a reference.  Returns true when the chunk was
  // already present (the dedup "hit"); *err set only on write failure.
  bool PutAndRef(const std::string& digest_hex, const char* data,
                 size_t len, bool* existed, std::string* err);

  // Drop one reference per entry of the recipe; chunks reaching zero are
  // unlinked.
  void UnrefAll(const Recipe& r);

  // Take one additional reference per recipe entry (recipe duplication:
  // CREATE_LINK of a chunked file).  False (and no refs taken) if any
  // chunk is absent.  All-or-nothing across digests: the involved
  // stripes are locked together in ascending index order.
  bool RefAll(const Recipe& r);

  // Is this chunk live (referenced by at least one recipe)?
  bool Has(const std::string& digest_hex) const;

  // Batched presence check, one lock acquisition PER STRIPE (not per
  // digest): byte i of the result is 0 when digests[i] is live, 1 when
  // it must be shipped.
  std::string HaveMask(const std::vector<std::string>& digests) const;

  // Take one reference on an already-live chunk; false when absent
  // (the replication receiver then reports the race and the sender
  // falls back to a full copy).  *stored_len (optional) gets the length
  // the store holds under the digest, read in the same acquisition (-1
  // when it knows none): a caller that reads the chunk through
  // ReadChunkSlices, which checks bounds only, holds its own length
  // against it first.
  bool RefOne(const std::string& digest_hex, int64_t* stored_len = nullptr);

  // Read one chunk fully into *out (resized).  False when missing/short.
  bool ReadChunk(const std::string& digest_hex, int64_t expect_len,
                 std::string* out) const;

  // Positional read of [offset, offset+len) of a chunk's payload into
  // dst (pread; no heap) — the cold-span path of the scatter-gather
  // download assembly.  False when missing/short.
  bool ReadChunkSlice(const std::string& digest_hex, int64_t offset,
                      int64_t len, char* dst) const;

  // One request of a batched cold-span round (ISSUE 18).
  struct SliceReq {
    const std::string* digest_hex = nullptr;  // borrowed for the call
    int64_t offset = 0;
    int64_t len = 0;
    char* dst = nullptr;
  };
  // Batched positional reads for one RecipeStream response round:
  // slab-resident chunks route through SlabStore::ReadSlices (one
  // preadv per contiguous slab run), everything else — flat, EC,
  // released — takes the per-request fallthrough.  *vec_batches /
  // *vec_spans accumulate the preadv syscall count and the requests
  // they served (the dio.preadv_* counter feed).  False on the first
  // unreadable chunk, with *failed naming its digest.
  bool ReadChunkSlices(const SliceReq* reqs, size_t n, int64_t* vec_batches,
                       int64_t* vec_spans, std::string* failed) const;

  // -- hot-chunk read cache ----------------------------------------------
  bool cache_enabled() const { return cache_.cap_bytes > 0; }
  // Cache lookup + disk read-through + insert, for DOWNLOAD_FILE: the
  // returned buffer is immutable and keep-alive (safe across eviction
  // and invalidation).  *hit reports whether the cache served it.
  // nullptr when the cache is off, the chunk is unreadable, or its size
  // does not match expect_len.  Inserts re-check liveness/quarantine
  // under the digest's stripe lock (see header comment).
  std::shared_ptr<const std::string> ReadChunkCached(
      const std::string& digest_hex, int64_t expect_len, bool* hit);
  // Lookup WITHOUT read-through or insert, for FETCH_CHUNK (recovery /
  // scrub-repair traffic must not evict client-hot chunks).
  std::shared_ptr<const std::string> CacheLookup(
      const std::string& digest_hex, int64_t expect_len);
  int64_t cache_hits() const { return cache_.hits.load(); }
  int64_t cache_misses() const { return cache_.misses.load(); }
  int64_t cache_evictions() const { return cache_.evictions.load(); }
  int64_t cache_invalidations() const { return cache_.invalidations.load(); }
  int64_t cache_bytes() const;
  int64_t cache_chunks() const;
  int64_t cache_capacity_bytes() const { return cache_.cap_bytes; }

  // Presence probe + pin in ONE stripe-lock acquisition per chunk, for
  // the negotiated upload's phase-1 answer: byte i of the result is 0
  // when chunk i is live (and now pinned against unlink until the
  // session's UnpinRecipe), 1 when the client must ship it.  A separate
  // HaveMask-then-PinRecipe would let a delete unlink a "present" chunk
  // in the gap; pinning absent digests is harmless (the unpin erases
  // the entry), so every entry is pinned and the whole recipe unpins.
  std::string PinAndMask(const Recipe& r);

  // Transient stream pins: an in-flight chunked download holds a pin per
  // recipe entry so a concurrent delete cannot unlink bytes it is still
  // sending (POSIX open-fd semantics for flat files, recreated here).
  // A pinned chunk whose refcount hits zero defers its unlink until the
  // last pin drops.  Pins are RAM-only — a crash loses only streams.
  void PinRecipe(const Recipe& r);
  void UnpinRecipe(const Recipe& r);

  // Read a recipe file and pin its chunks, failing before the first
  // byte: each chunk is verified still-referenced and pinned under its
  // stripe lock; if any chunk was already unreferenced (a concurrent
  // delete), the pins taken so far roll back and the caller fails the
  // download with ENOENT — never mid-stream.
  std::optional<Recipe> ReadRecipeAndPin(const std::string& path);

  // Ranged variant for the parallel download client: pin (and return)
  // ONLY the recipe entries overlapping [offset, offset+count) of the
  // logical file (count 0 = to EOF) — a 4-range parallel download of a
  // many-thousand-chunk file must not pay 4x full-recipe pin/unpin and
  // skip scans.  The returned Recipe keeps the FULL logical_size but
  // holds just the overlapping chunk slice; *skip_out is the byte
  // offset inside its first entry.  UnpinRecipe on the returned
  // (trimmed) recipe releases exactly the pins taken.  nullopt (no
  // pins) when the recipe is gone or a chunk was unreferenced; offset
  // PAST EOF returns an EMPTY slice instead, so the caller can tell
  // "bad range" (EINVAL, by logical_size) from "gone" (ENOENT).
  std::optional<Recipe> ReadRecipeAndPinRange(const std::string& path,
                                              int64_t offset, int64_t count,
                                              int64_t* skip_out);

  std::string ChunkPath(const std::string& digest_hex) const;
  std::string QuarantinePath(const std::string& digest_hex) const;

  // -- recipe sidecars (slab-aware; storage/slabstore.h) -----------------
  // All take the recipe's SIDECAR PATH (<local>.rcp) like the old
  // file-level codec did; small recipes land as slab records keyed by
  // that path relative to the store root, large ones stay flat files.
  // Loads consult both layouts, so a threshold change never strands
  // existing data.
  bool StoreRecipe(const std::string& rcp_path, const Recipe& r,
                   std::string* err);
  std::optional<Recipe> LoadRecipe(const std::string& rcp_path) const;
  bool HasRecipe(const std::string& rcp_path) const;
  // Remove whichever representation exists; *bytes_out (optional) gets
  // the on-disk bytes reclaimed (scrub.bytes_reclaimed accounting).
  // False when no recipe existed under the path.
  bool RemoveRecipe(const std::string& rcp_path, int64_t* bytes_out);

  // -- slab packing ------------------------------------------------------
  bool slab_enabled() const { return slab_ != nullptr; }
  SlabStore* slab() { return slab_.get(); }  // tests / stats plumbing
  // slab.* registry gauges (all 0 when packing is off).
  int64_t slab_files() const { return slab_ ? slab_->files() : 0; }
  int64_t slab_slots_live() const { return slab_ ? slab_->slots_live() : 0; }
  int64_t slab_slots_dead() const { return slab_ ? slab_->slots_dead() : 0; }
  int64_t slab_bytes_live() const { return slab_ ? slab_->bytes_live() : 0; }
  int64_t slab_bytes_dead() const { return slab_ ? slab_->bytes_dead() : 0; }
  int64_t slab_compactions() const {
    return slab_ ? slab_->compactions() : 0;
  }
  int64_t slab_compacted_bytes() const {
    return slab_ ? slab_->compacted_bytes() : 0;
  }

  // -- erasure-coded cold tier (storage/ecstore.h) -----------------------
  struct ChunkInfo {
    std::string digest_hex;
    int64_t length = 0;
  };
  bool ec_enabled() const { return ec_ != nullptr; }
  EcStore* ec() { return ec_.get(); }  // scrub stage 5 / tests / stats
  const EcStore* ec() const { return ec_.get(); }
  // ec.* registry gauges (all 0 when the tier is off).
  int64_t ec_stripes() const { return ec_ ? ec_->stripes() : 0; }
  int64_t ec_stripe_chunks() const {
    return ec_ ? ec_->stripe_chunks() : 0;
  }
  int64_t ec_data_bytes() const { return ec_ ? ec_->data_bytes() : 0; }
  int64_t ec_parity_bytes() const { return ec_ ? ec_->parity_bytes() : 0; }
  int64_t released_chunks() const { return released_chunks_.load(); }
  int64_t released_bytes() const { return released_bytes_.load(); }
  int64_t ec_remote_reads() const { return remote_reads_.load(); }

  // Remote-replica fetch for RELEASED chunks: the server installs a
  // group-peer FETCH_CHUNK round here at startup.  Called WITHOUT any
  // lock held (it does network IO); the returned bytes are SHA1-checked
  // by the caller before serving.  Null = released chunks read as
  // missing (single-node stores).
  using RemoteFetchFn = std::function<bool(
      const std::string& digest_hex, int64_t length, std::string* out)>;
  void set_remote_fetch(RemoteFetchFn fn) { remote_fetch_ = std::move(fn); }

  // Demotion candidates for scrub stage 5: live, unpinned,
  // unquarantined, unreleased, not yet EC-resident, and COLD — payload
  // mtime (flat file stat / slab record meta) at or past age_s seconds
  // old at now_s.  The mtime probes run lock-free after a locked
  // candidate scan, so a many-million-chunk store never stats under a
  // stripe lock.
  std::vector<ChunkInfo> SnapshotDemotable(int64_t now_s,
                                           int64_t age_s) const;

  // Owner-side demotion: read + SHA1-verify each chunk, encode ONE
  // RS(k, m) stripe, re-verify it from disk through the decode path,
  // then drop the local flat/slab payloads (refs/lens stay — reads fall
  // through to the stripe).  Chunks that vanished, fail their hash, or
  // are already EC-resident are skipped silently (the next pass
  // re-snapshots).  Returns the stripe id, or -1 with *err (nothing
  // demoted — a failed verify also unwinds the stripe).
  int64_t DemoteToEc(const std::vector<ChunkInfo>& chunks,
                     int64_t* chunks_demoted, int64_t* bytes_demoted,
                     std::string* err);

  // Peer-side EC_RELEASE: drop the local replica of chunks the group
  // owner now holds in parity.  Byte i of the result is 0 when chunk i
  // is released here (or was never held — nothing retained either way),
  // 1 when it is KEPT (pinned by an in-flight stream, or quarantined —
  // the scrub repair machinery owns that lifecycle).  Idempotent: a
  // replayed release of an already-released digest answers 0.  Released
  // marks are journaled to data/released.log before the response so a
  // crash cannot resurrect a dropped replica as "held".
  std::string ReleaseChunks(const std::vector<ChunkInfo>& chunks);
  bool IsReleased(const std::string& digest_hex) const;

  // -- integrity engine (storage/scrub.*) --------------------------------
  // Live (referenced, non-quarantined) chunks for a verify pass.
  // prefix -1 snapshots everything in one call; 0..255 filters to
  // digests whose first byte equals it, so a scrubber walking the 256
  // slices in turn holds one stripe lock for one allocation-light
  // filter scan at a time and never keeps a many-million-entry
  // snapshot resident across an hours-long paced pass.
  std::vector<ChunkInfo> SnapshotLive(int prefix = -1) const;
  // Currently quarantined chunks still named by a recipe (repair targets).
  std::vector<ChunkInfo> SnapshotQuarantined() const;
  bool IsQuarantined(const std::string& digest_hex) const;

  enum class QuarantineResult { kQuarantined, kGone, kPinned, kClean };
  // Move a corrupt chunk's bytes aside so no download/replication path
  // ever serves them again.  kPinned when an in-flight stream still
  // holds the chunk (repair-in-place under a reader is not safe — the
  // scrubber retries next pass); kGone when the chunk lost its last
  // reference meanwhile; kClean when a re-read UNDER THE LOCK hashes
  // correctly — the caller's lock-free verify read raced a delete +
  // re-upload of the same digest, and the bytes on disk now are good
  // (quarantining them would jail a freshly-written chunk).  Probe,
  // re-verify, rename, and read-cache invalidation happen in one
  // stripe-lock acquisition, which no PutAndRef/UnrefAll of this
  // digest can interleave.
  QuarantineResult Quarantine(const std::string& digest_hex);
  // Restore verified bytes for a still-referenced digest (replica
  // repair).  False when the digest is no longer live (deleted — drop
  // it) or the write fails.  The caller MUST have verified
  // SHA1(data) == digest_hex.
  bool RepairChunk(const std::string& digest_hex, const char* data,
                   size_t len, std::string* err);
  // Reclaim zero-ref chunks whose grace expired at `now_s`, skipping
  // pinned ones — probe and unlink under one stripe-lock acquisition,
  // so a concurrent PinAndMask either pinned the chunk first (sweep
  // skips it) or finds it already gone (reports it as needed).
  // Returns the number of chunks unlinked; *bytes accumulates sizes.
  int64_t GcSweep(int64_t now_s, int64_t* bytes);

  // Paced online compaction of dead slab space (driven from the scrub
  // pass, sharing its token bucket via `pace` and its shutdown flag via
  // `stop`).  Chunk records that failed the copy-time re-verify come
  // back in *corrupt so the caller can route them through the standard
  // quarantine/repair machinery (ScrubManager::HandleCorrupt); corrupt
  // recipe records are only counted — their files fail loudly on read
  // and heal via replica re-sync.  Returns slabs reclaimed; *reclaimed
  // accumulates unlinked slab-file bytes.  No-op when packing is off.
  int64_t CompactSlabs(const std::function<void(int64_t)>& pace,
                       const std::function<bool()>& stop,
                       std::vector<ChunkInfo>* corrupt, int64_t* reclaimed);

  int64_t unique_chunks() const;
  int64_t unique_bytes() const { return unique_bytes_.load(); }
  int64_t gc_pending_chunks() const;
  int64_t gc_pending_bytes() const { return zero_ref_bytes_.load(); }
  int64_t quarantined_chunks() const;

 private:
  struct ZeroRef {
    int64_t length = 0;
    int64_t since_s = 0;  // wall clock of the last unref (or file mtime)
  };
  // One lock stripe: all per-digest state for digests whose first hex
  // nibble selects this stripe lives here, guarded by `mu`.
  struct Stripe {
    mutable RankedMutex mu{LockRank::kChunkStripe};
    std::unordered_map<std::string, int64_t> refs;
    std::unordered_map<std::string, int64_t> lens;  // digest -> byte length
    std::unordered_map<std::string, int64_t> pins;  // in-flight streams
    std::unordered_map<std::string, ZeroRef> zero_ref;  // awaiting GC
    std::unordered_set<std::string> quarantined;
    // Replica dropped via EC_RELEASE (group owner holds the bytes in
    // parity); refs/lens entries remain, reads remote-fetch.
    std::unordered_set<std::string> released;
  };
  static constexpr int kStripes = 16;
  static int StripeIndex(const std::string& digest_hex);
  Stripe& StripeFor(const std::string& digest_hex) {
    return stripes_[StripeIndex(digest_hex)];
  }
  const Stripe& StripeFor(const std::string& digest_hex) const {
    return stripes_[StripeIndex(digest_hex)];
  }

  // stripe mu held.  Park a zero-ref chunk for GC or unlink it eagerly
  // (gc_grace_s_ == 0 and unpinned).
  void RetireLocked(Stripe& s, const std::string& digest_hex,
                    int64_t length);
  // stripe mu held.  Unlink a zero-ref chunk's bytes (chunks/,
  // quarantine/, any slab record, any EC slot, any released mark) and
  // invalidate any cached copy.
  void UnlinkRetiredLocked(Stripe& s, const std::string& digest_hex);
  // stripe mu held.  Drop just the LOCAL PAYLOAD (flat file / slab
  // record + cached copy), keeping refs/lens/quarantine state — the
  // shared core of UnlinkRetiredLocked (full retirement), DemoteToEc
  // (bytes now live in the EC stripe), and ReleaseChunks (bytes now
  // live on the group owner).
  void DropPayloadLocked(Stripe& s, const std::string& digest_hex);
  // stripe mu held.  Clear a released mark because verified bytes just
  // landed locally (heal-on-upload, replica repair); journals 'H'.
  void UnreleaseLocked(Stripe& s, const std::string& digest_hex,
                       int64_t len);
  std::string ReleasedLogPath() const {
    return store_path_ + "/data/released.log";
  }
  // Append released.log records ('R' digest len / 'H' digest) with one
  // fsync per call — ReleaseChunks batches a whole EC_RELEASE body into
  // one append so the journal is durable before the response commits
  // the owner to dropping coverage.
  void AppendReleasedLog(const std::string& records) const;
  // Should a fresh chunk payload of this size land in the slab store?
  bool SlabChunkEligible(int64_t len) const {
    return slab_ != nullptr && slab_opts_.chunk_threshold > 0 &&
           len < slab_opts_.chunk_threshold;
  }
  // stripe mu held.  Write/replace a chunk payload in whichever layout
  // its size selects (slab record or flat file) — the shared landing
  // path of PutAndRef's first write, heal-on-upload, and RepairChunk.
  bool WriteChunkPayloadLocked(const std::string& digest_hex,
                               const char* data, size_t len,
                               std::string* err);
  // Slab key for a recipe sidecar path (relative to the store root).
  std::string RecipeSlabKey(const std::string& rcp_path) const;

  // -- read cache internals ----------------------------------------------
  struct CacheEntry {
    std::string digest_hex;
    std::shared_ptr<const std::string> data;
  };
  struct ReadCache {
    int64_t cap_bytes = 0;
    mutable RankedMutex mu{LockRank::kReadCache};
    std::list<CacheEntry> lru;  // front = most recent
    std::unordered_map<std::string, std::list<CacheEntry>::iterator> index;
    int64_t bytes = 0;
    std::atomic<int64_t> hits{0}, misses{0}, evictions{0},
        invalidations{0};
  };
  std::shared_ptr<const std::string> CacheGet(const std::string& digest_hex);
  // Insert (caller holds NO stripe lock; this re-takes the digest's
  // stripe lock to re-check liveness — see header comment).
  void CacheInsertIfLive(const std::string& digest_hex,
                         std::shared_ptr<const std::string> data);
  // stripe mu held (or startup): drop a digest's cached copy.
  void CacheInvalidate(const std::string& digest_hex);
  void CacheClear();

  std::string store_path_;
  int64_t gc_grace_s_ = 0;
  SlabOptions slab_opts_;
  std::unique_ptr<SlabStore> slab_;  // null = flat layout only
  std::unique_ptr<EcStore> ec_;      // null = no erasure-coded tier
  RemoteFetchFn remote_fetch_;
  class EventLog* events_ = nullptr;
  std::array<Stripe, kStripes> stripes_;
  std::atomic<int64_t> unique_bytes_{0};
  std::atomic<int64_t> zero_ref_bytes_{0};
  std::atomic<int64_t> released_chunks_{0};
  std::atomic<int64_t> released_bytes_{0};
  // Counted from const read paths (the fallthrough serve), hence mutable.
  mutable std::atomic<int64_t> remote_reads_{0};
  mutable ReadCache cache_;
};

}  // namespace fdfs
