#include "storage/chunkstore.h"

#include <dirent.h>
#include <fcntl.h>
#include <string.h>
#include <sys/stat.h>
#include <unistd.h>

#include <cerrno>
#include <cstdio>
#include <ctime>

#include "common/bytes.h"
#include "common/eventlog.h"
#include "common/fsutil.h"
#include "common/log.h"

namespace fdfs {

namespace {

constexpr char kRecipeMagic[8] = {'F', 'D', 'F', 'S', 'R', 'C', 'P', '1'};

bool IsHex40(const std::string& s) {
  if (s.size() != 40) return false;
  for (char c : s)
    if (!((c >= '0' && c <= '9') || (c >= 'a' && c <= 'f'))) return false;
  return true;
}

}  // namespace

// -- recipe codec ---------------------------------------------------------
// Layout: 8B magic, 8B logical_size BE, 8B chunk_count BE, then per chunk
// 20B raw digest + 8B length BE.  Offsets are implicit (cumulative).
// The buffer forms are shared between .rcp sidecar files and slab-packed
// recipe records — identical bytes in both layouts.

std::string EncodeRecipe(const Recipe& r) {
  std::string buf(kRecipeMagic, sizeof(kRecipeMagic));
  uint8_t num[8];
  PutInt64BE(r.logical_size, num);
  buf.append(reinterpret_cast<char*>(num), 8);
  PutInt64BE(static_cast<int64_t>(r.chunks.size()), num);
  buf.append(reinterpret_cast<char*>(num), 8);
  for (const RecipeEntry& e : r.chunks) {
    for (size_t i = 0; i < 40; i += 2) {
      buf.push_back(static_cast<char>(
          strtoul(e.digest_hex.substr(i, 2).c_str(), nullptr, 16)));
    }
    PutInt64BE(e.length, num);
    buf.append(reinterpret_cast<char*>(num), 8);
  }
  return buf;
}

std::optional<Recipe> DecodeRecipe(const char* data, size_t len) {
  if (len < 24 || memcmp(data, kRecipeMagic, sizeof(kRecipeMagic)) != 0)
    return std::nullopt;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(data);
  Recipe r;
  r.logical_size = GetInt64BE(p + 8);
  int64_t count = GetInt64BE(p + 16);
  if (count < 0 || count > (1 << 26))  // 64M chunks ~= 0.5 PB file
    return std::nullopt;
  if (len < 24 + static_cast<size_t>(count) * 28) return std::nullopt;
  static const char* kHex = "0123456789abcdef";
  r.chunks.reserve(static_cast<size_t>(count));
  for (int64_t i = 0; i < count; ++i) {
    const uint8_t* rec = p + 24 + i * 28;
    RecipeEntry e;
    e.digest_hex.resize(40);
    for (int b = 0; b < 20; ++b) {
      e.digest_hex[2 * b] = kHex[rec[b] >> 4];
      e.digest_hex[2 * b + 1] = kHex[rec[b] & 0xF];
    }
    e.length = GetInt64BE(rec + 20);
    if (e.length < 0) return std::nullopt;
    r.chunks.push_back(std::move(e));
  }
  return r;
}

bool WriteRecipeFile(const std::string& path, const Recipe& r,
                     std::string* err) {
  std::string tmp = path + ".tmp";
  FILE* f = fopen(tmp.c_str(), "wb");
  if (f == nullptr) {
    *err = "open " + tmp + ": " + strerror(errno);
    return false;
  }
  std::string buf = EncodeRecipe(r);
  bool ok = fwrite(buf.data(), 1, buf.size(), f) == buf.size() &&
            fflush(f) == 0 && fsync(fileno(f)) == 0;
  fclose(f);
  if (!ok || rename(tmp.c_str(), path.c_str()) != 0) {
    *err = "write " + path + ": " + strerror(errno);
    unlink(tmp.c_str());
    return false;
  }
  return true;
}

std::optional<Recipe> ReadRecipeFile(const std::string& path) {
  std::string buf;
  if (!ReadWholeFile(path, &buf)) return std::nullopt;
  return DecodeRecipe(buf.data(), buf.size());
}

// -- store ----------------------------------------------------------------

ChunkStore::ChunkStore(std::string store_path, int64_t gc_grace_s,
                       int64_t read_cache_bytes, SlabOptions slab, int ec_k,
                       int ec_m)
    : store_path_(std::move(store_path)),
      gc_grace_s_(gc_grace_s < 0 ? 0 : gc_grace_s),
      slab_opts_(slab) {
  cache_.cap_bytes = read_cache_bytes < 0 ? 0 : read_cache_bytes;
  // The slab store exists whenever packing is configured OR slab data
  // is already on disk: thresholds gate only NEW writes.  An operator
  // draining the layout (both thresholds 0, OPERATIONS.md) must keep
  // reading slab-resident records — without this, boot would treat
  // every chunk named only by a slab-resident recipe as an orphan and
  // GC it: data loss, not a drain.
  struct stat st;
  bool slabs_on_disk =
      stat((store_path_ + "/data/slabs").c_str(), &st) == 0 &&
      S_ISDIR(st.st_mode);
  if (slab_opts_.chunk_threshold > 0 || slab_opts_.recipe_threshold > 0 ||
      slabs_on_disk)
    slab_ = std::make_unique<SlabStore>(store_path_ + "/data/slabs",
                                        slab_opts_.slab_bytes,
                                        slab_opts_.compact_min_dead_pct);
  // Same drain discipline for the EC tier: ec_k = 0 with stripes on
  // disk mounts the store read-only (Rescan adopts the on-disk
  // geometry; EncodeStripe refuses) so demoted chunks stay readable
  // while scrub repair / deletes drain the stripes.
  bool ec_on_disk = stat((store_path_ + "/data/ec").c_str(), &st) == 0 &&
                    S_ISDIR(st.st_mode);
  if (ec_k > 0 || ec_on_disk)
    ec_ = std::make_unique<EcStore>(store_path_ + "/data/ec",
                                    ec_k > 0 ? ec_k : 0,
                                    ec_k > 0 ? ec_m : 0);
  // Stripe locks share one rank; the index is the ascending-protocol
  // order key the FDFS_LOCKRANK checker validates RefAll against.
  for (int i = 0; i < kStripes; ++i) stripes_[i].mu.set_order_key(i);
}

int ChunkStore::StripeIndex(const std::string& digest_hex) {
  // First hex nibble of the digest: SHA1 is uniform, so the 16 stripes
  // load-balance by construction.  Non-hex input (never produced by the
  // callers) still lands in a valid stripe.
  char c = digest_hex.empty() ? '0' : digest_hex[0];
  if (c >= '0' && c <= '9') return c - '0';
  if (c >= 'a' && c <= 'f') return c - 'a' + 10;
  return 0;
}

std::string ChunkStore::ChunkPath(const std::string& digest_hex) const {
  return store_path_ + "/data/chunks/" + digest_hex.substr(0, 2) + "/" +
         digest_hex.substr(2, 2) + "/" + digest_hex;
}

std::string ChunkStore::QuarantinePath(const std::string& digest_hex) const {
  return store_path_ + "/data/quarantine/" + digest_hex;
}

namespace {

// Write-if-absent payload write (tmp + rename; a leftover file from a
// crashed write is simply overwritten — content-addressed, so same
// digest => same bytes).
bool WriteChunkFile(const std::string& path, const char* data, size_t len,
                    std::string* err) {
  std::string tmp = path + ".tmp";
  int fd = open(tmp.c_str(), O_CREAT | O_WRONLY | O_TRUNC, 0644);
  if (fd < 0) {
    *err = "open " + tmp + ": " + strerror(errno);
    return false;
  }
  size_t off = 0;
  while (off < len) {
    ssize_t w = write(fd, data + off, len - off);
    if (w <= 0) {
      *err = "write " + tmp + ": " + strerror(errno);
      close(fd);
      unlink(tmp.c_str());
      return false;
    }
    off += static_cast<size_t>(w);
  }
  close(fd);
  if (rename(tmp.c_str(), path.c_str()) != 0) {
    *err = "rename " + path + ": " + strerror(errno);
    unlink(tmp.c_str());
    return false;
  }
  return true;
}

}  // namespace

bool ChunkStore::WriteChunkPayloadLocked(const std::string& digest_hex,
                                         const char* data, size_t len,
                                         std::string* err) {
  // stripe mu held.  The shared payload landing path: first writes,
  // heal-on-upload, and replica repair all route here so the slab-vs-
  // flat layout decision lives in exactly one place.
  if (SlabChunkEligible(static_cast<int64_t>(len))) {
    // Replace semantics mark any older record (a quarantined original,
    // a pre-repair copy) dead in place; a stale flat twin from before a
    // threshold change is dropped so it can never shadow the record.
    if (!slab_->Append(kSlabKindChunk, digest_hex, data, len,
                       /*durable=*/false, err))
      return false;
    unlink(ChunkPath(digest_hex).c_str());
    return true;
  }
  std::string path = ChunkPath(digest_hex);
  EnsureParentDirs(path);
  if (!WriteChunkFile(path, data, len, err)) return false;
  if (slab_ != nullptr) slab_->MarkDead(kSlabKindChunk, digest_hex);
  return true;
}

bool ChunkStore::PutAndRef(const std::string& digest_hex, const char* data,
                           size_t len, bool* existed, std::string* err) {
  Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  // Heal-on-upload: these bytes hash to the digest (every caller
  // verifies before PutAndRef), so a quarantined chunk gets its good
  // payload restored by ANY upload/replication that carries it.
  // Best-effort — a failed restore leaves the chunk quarantined
  // (downloads keep failing loudly) but never fails the upload, which
  // historically never wrote in the already-present case.
  auto heal = [&]() {
    if (!st.quarantined.count(digest_hex)) return;
    std::string werr;
    if (WriteChunkPayloadLocked(digest_hex, data, len, &werr)) {
      st.quarantined.erase(digest_hex);
      unlink(QuarantinePath(digest_hex).c_str());
      CacheInvalidate(digest_hex);
      FDFS_LOG_INFO("chunk %s healed by incoming payload",
                    digest_hex.c_str());
      if (events_ != nullptr)
        events_->Record(EventSeverity::kInfo, "chunk.healed", digest_hex,
                        "by=upload bytes=" + std::to_string(len));
    } else {
      FDFS_LOG_WARN("quarantined chunk %s heal failed: %s",
                    digest_hex.c_str(), werr.c_str());
    }
  };
  // Released chunks heal the same way: the upload carries verified
  // bytes, so the local replica returns and the remote-fetch dependency
  // on the group owner ends.
  auto unrelease = [&]() {
    if (!st.released.count(digest_hex)) return;
    std::string werr;
    if (WriteChunkPayloadLocked(digest_hex, data, len, &werr))
      UnreleaseLocked(st, digest_hex, static_cast<int64_t>(len));
    else
      FDFS_LOG_WARN("released chunk %s re-materialize failed: %s",
                    digest_hex.c_str(), werr.c_str());
  };
  auto it = st.refs.find(digest_hex);
  if (it != st.refs.end()) {
    heal();
    unrelease();
    it->second++;
    *existed = true;
    return true;
  }
  auto z = st.zero_ref.find(digest_hex);
  if (z != st.zero_ref.end()) {
    // Zero-ref but still on disk (GC grace window, or a pinned stream
    // deferring the unlink): resurrect instead of rewriting.
    heal();
    st.refs[digest_hex] = 1;
    st.lens[digest_hex] = z->second.length;
    unique_bytes_ += z->second.length;
    zero_ref_bytes_ -= z->second.length;
    st.zero_ref.erase(z);
    *existed = true;
    return true;
  }
  // First reference: write the payload (slab record below the packing
  // threshold, flat file otherwise).
  if (!WriteChunkPayloadLocked(digest_hex, data, len, err)) return false;
  st.refs[digest_hex] = 1;
  st.lens[digest_hex] = static_cast<int64_t>(len);
  unique_bytes_ += static_cast<int64_t>(len);
  *existed = false;
  return true;
}

bool ChunkStore::RefAll(const Recipe& r) {
  // All-or-nothing across digests: lock every involved stripe together,
  // in ascending index order (the ordered multi-stripe protocol), so no
  // UnrefAll can interleave between the presence check and the refs.
  bool involved[kStripes] = {};
  for (const RecipeEntry& e : r.chunks) involved[StripeIndex(e.digest_hex)] = true;
  std::array<std::unique_lock<RankedMutex>, kStripes> locks;
  for (int i = 0; i < kStripes; ++i)
    if (involved[i]) locks[i] = std::unique_lock<RankedMutex>(stripes_[i].mu);
  for (const RecipeEntry& e : r.chunks)
    if (StripeFor(e.digest_hex).refs.find(e.digest_hex) ==
        StripeFor(e.digest_hex).refs.end())
      return false;
  for (const RecipeEntry& e : r.chunks)
    StripeFor(e.digest_hex).refs[e.digest_hex]++;
  return true;
}

bool ChunkStore::Has(const std::string& digest_hex) const {
  const Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  return st.refs.find(digest_hex) != st.refs.end();
}

std::string ChunkStore::HaveMask(
    const std::vector<std::string>& digests) const {
  // One lock acquisition per stripe (not per digest): group the batch
  // by stripe, then answer each stripe's subset under its lock.
  std::string need(digests.size(), '\0');
  std::vector<uint32_t> by_stripe[kStripes];
  for (size_t i = 0; i < digests.size(); ++i)
    by_stripe[StripeIndex(digests[i])].push_back(static_cast<uint32_t>(i));
  for (int s = 0; s < kStripes; ++s) {
    if (by_stripe[s].empty()) continue;
    const Stripe& st = stripes_[s];
    std::lock_guard<RankedMutex> lk(st.mu);
    for (uint32_t i : by_stripe[s])
      need[i] = st.refs.find(digests[i]) != st.refs.end() &&
                        !st.quarantined.count(digests[i])
                    ? 0 : 1;
  }
  return need;
}

bool ChunkStore::RefOne(const std::string& digest_hex, int64_t* stored_len) {
  Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  auto it = st.refs.find(digest_hex);
  if (it == st.refs.end()) return false;
  it->second++;
  if (stored_len != nullptr) {
    auto l = st.lens.find(digest_hex);
    *stored_len = l != st.lens.end() ? l->second : -1;
  }
  return true;
}

void ChunkStore::RetireLocked(Stripe& s, const std::string& digest_hex,
                              int64_t length) {
  // stripe mu held; refs entry already erased.  Eager mode (no GC
  // grace) keeps the original semantics: unlink now unless an in-flight
  // stream pins the chunk, in which case the zero_ref entry defers the
  // unlink to the last UnpinRecipe.  With a grace window every zero-ref
  // chunk parks for the scrubber's GcSweep.
  unique_bytes_ -= length;
  if (gc_grace_s_ == 0 && !s.pins.count(digest_hex)) {
    UnlinkRetiredLocked(s, digest_hex);
    return;
  }
  s.zero_ref[digest_hex] = ZeroRef{length, time(nullptr)};
  zero_ref_bytes_ += length;
}

void ChunkStore::DropPayloadLocked(Stripe& s,
                                   const std::string& digest_hex) {
  (void)s;  // the stripe lock is the contract, not an input
  if (slab_ != nullptr) slab_->MarkDead(kSlabKindChunk, digest_hex);
  unlink(ChunkPath(digest_hex).c_str());
  // Strict cache coherence: a dropped payload must never be served from
  // the read cache (a later re-materialization re-admits it).
  CacheInvalidate(digest_hex);
}

void ChunkStore::UnlinkRetiredLocked(Stripe& s,
                                     const std::string& digest_hex) {
  DropPayloadLocked(s, digest_hex);
  unlink(QuarantinePath(digest_hex).c_str());
  s.quarantined.erase(digest_hex);
  // Full retirement also reclaims the chunk's EC slot (parity bytes
  // come back when its stripe's last live chunk dies) and any released
  // mark — a deleted chunk needs no remote serve path.
  if (ec_ != nullptr) ec_->MarkDead(digest_hex, nullptr);
  if (s.released.erase(digest_hex) > 0) {
    released_chunks_--;
    auto l = s.lens.find(digest_hex);
    released_bytes_ -= l != s.lens.end() ? l->second : 0;
  }
  s.lens.erase(digest_hex);
}

void ChunkStore::UnrefAll(const Recipe& r) {
  for (const RecipeEntry& e : r.chunks) {
    Stripe& st = StripeFor(e.digest_hex);
    std::lock_guard<RankedMutex> lk(st.mu);
    auto it = st.refs.find(e.digest_hex);
    if (it == st.refs.end()) continue;
    if (--it->second <= 0) {
      st.refs.erase(it);
      RetireLocked(st, e.digest_hex, e.length);
    }
  }
}

std::optional<Recipe> ChunkStore::ReadRecipeAndPin(const std::string& path) {
  // The recipe read needs no lock (both layouts are immutable once
  // published); the verify-refs-then-pin per chunk under its stripe
  // lock is what closes the race with a concurrent delete.  If any
  // chunk already lost its references (the file is mid-delete) the
  // pins taken so far roll back and the download fails with ENOENT
  // before the first byte — never mid-stream.
  auto r = LoadRecipe(path);
  if (!r.has_value()) return std::nullopt;
  for (size_t i = 0; i < r->chunks.size(); ++i) {
    Stripe& st = StripeFor(r->chunks[i].digest_hex);
    std::unique_lock<RankedMutex> lk(st.mu);
    if (st.refs.find(r->chunks[i].digest_hex) == st.refs.end()) {
      lk.unlock();
      Recipe taken;
      taken.chunks.assign(r->chunks.begin(), r->chunks.begin() + i);
      UnpinRecipe(taken);
      return std::nullopt;
    }
    st.pins[r->chunks[i].digest_hex]++;
  }
  return r;
}

std::optional<Recipe> ChunkStore::ReadRecipeAndPinRange(
    const std::string& path, int64_t offset, int64_t count,
    int64_t* skip_out) {
  auto full = LoadRecipe(path);
  if (!full.has_value() || offset < 0) return std::nullopt;
  // offset past EOF yields an EMPTY slice (no pins) rather than
  // nullopt, so the caller can distinguish "gone" (ENOENT) from "bad
  // range" (EINVAL) by logical_size.
  int64_t want = full->logical_size - offset;
  if (count > 0 && count < want) want = count;
  // Locate the overlapping slice (one pass; the recipe is already in
  // memory from the parse).
  Recipe trimmed;
  trimmed.logical_size = full->logical_size;
  size_t first = 0;
  int64_t skip = offset;
  while (first < full->chunks.size() &&
         skip >= full->chunks[first].length) {
    skip -= full->chunks[first].length;
    ++first;
  }
  size_t last = first;
  int64_t covered = -skip;
  while (last < full->chunks.size() && covered < want)
    covered += full->chunks[last++].length;
  trimmed.chunks.assign(full->chunks.begin() + first,
                        full->chunks.begin() + last);
  // Verify+pin per chunk with rollback, exactly like ReadRecipeAndPin.
  for (size_t i = 0; i < trimmed.chunks.size(); ++i) {
    Stripe& st = StripeFor(trimmed.chunks[i].digest_hex);
    std::unique_lock<RankedMutex> lk(st.mu);
    if (st.refs.find(trimmed.chunks[i].digest_hex) == st.refs.end()) {
      lk.unlock();
      Recipe taken;
      taken.chunks.assign(trimmed.chunks.begin(),
                          trimmed.chunks.begin() + i);
      UnpinRecipe(taken);
      return std::nullopt;
    }
    st.pins[trimmed.chunks[i].digest_hex]++;
  }
  *skip_out = skip;
  return trimmed;
}

std::string ChunkStore::PinAndMask(const Recipe& r) {
  std::string need(r.chunks.size(), '\0');
  for (size_t i = 0; i < r.chunks.size(); ++i) {
    // Quarantined chunks read as "needed": the client re-ships the
    // bytes and PutAndRef heals the store.  The pin taken here also
    // exempts the chunk from GcSweep and Quarantine for the session's
    // lifetime — probe and pin share this one stripe-lock acquisition.
    Stripe& st = StripeFor(r.chunks[i].digest_hex);
    std::lock_guard<RankedMutex> lk(st.mu);
    need[i] = st.refs.find(r.chunks[i].digest_hex) != st.refs.end() &&
                      !st.quarantined.count(r.chunks[i].digest_hex)
                  ? 0 : 1;
    st.pins[r.chunks[i].digest_hex]++;
  }
  return need;
}

void ChunkStore::PinRecipe(const Recipe& r) {
  for (const RecipeEntry& e : r.chunks) {
    Stripe& st = StripeFor(e.digest_hex);
    std::lock_guard<RankedMutex> lk(st.mu);
    st.pins[e.digest_hex]++;
  }
}

void ChunkStore::UnpinRecipe(const Recipe& r) {
  for (const RecipeEntry& e : r.chunks) {
    Stripe& st = StripeFor(e.digest_hex);
    std::lock_guard<RankedMutex> lk(st.mu);
    auto it = st.pins.find(e.digest_hex);
    if (it == st.pins.end()) continue;
    if (--it->second <= 0) {
      st.pins.erase(it);
      // Eager mode: the last pin drop completes a delete that was
      // deferred mid-stream — unless the chunk was re-added while the
      // stream ran (PutAndRef resurrection erased the zero_ref entry).
      // With a GC grace the entry simply waits for GcSweep.
      auto z = st.zero_ref.find(e.digest_hex);
      if (z != st.zero_ref.end() && gc_grace_s_ == 0 &&
          st.refs.find(e.digest_hex) == st.refs.end()) {
        zero_ref_bytes_ -= z->second.length;
        st.zero_ref.erase(z);
        UnlinkRetiredLocked(st, e.digest_hex);
      }
    }
  }
}

bool ChunkStore::ReadChunk(const std::string& digest_hex, int64_t expect_len,
                           std::string* out) const {
  // Slab-resident chunks read as extents of their slab record; the
  // length check keeps the flat path's "short file is corrupt"
  // semantics.  Absent from the slot index => the flat layout owns it.
  if (slab_ != nullptr) {
    SlabStore::Slot slot;
    if (slab_->Lookup(kSlabKindChunk, digest_hex, &slot)) {
      if (slot.payload_len != expect_len) return false;
      return slab_->Read(kSlabKindChunk, digest_hex, out);
    }
  }
  int fd = open(ChunkPath(digest_hex).c_str(), O_RDONLY);
  if (fd >= 0) {
    out->resize(static_cast<size_t>(expect_len));
    size_t off = 0;
    while (off < out->size()) {
      ssize_t r = read(fd, out->data() + off, out->size() - off);
      if (r <= 0) {
        close(fd);
        return false;
      }
      off += static_cast<size_t>(r);
    }
    close(fd);
    return true;
  }
  // Cold-tier fallthrough: an EC-resident chunk (payload demoted into a
  // local RS stripe) decodes transparently.
  if (ec_ != nullptr && ec_->ReadChunk(digest_hex, out) &&
      static_cast<int64_t>(out->size()) == expect_len)
    return true;
  // Released replica: the group owner holds the bytes (in parity);
  // fetch them back over the wire, SHA1-gated.  The hook runs with NO
  // lock held — network IO under a stripe lock would convoy the store.
  if (remote_fetch_ != nullptr) {
    bool released;
    {
      const Stripe& st = StripeFor(digest_hex);
      std::lock_guard<RankedMutex> lk(st.mu);
      released = st.released.count(digest_hex) != 0;
    }
    if (released) {
      std::string buf;
      if (remote_fetch_(digest_hex, expect_len, &buf) &&
          static_cast<int64_t>(buf.size()) == expect_len &&
          Sha1(buf.data(), buf.size()).Hex() == digest_hex) {
        remote_reads_.fetch_add(1, std::memory_order_relaxed);
        *out = std::move(buf);
        return true;
      }
    }
  }
  return false;
}

bool ChunkStore::ReadChunkSlice(const std::string& digest_hex,
                                int64_t offset, int64_t len,
                                char* dst) const {
  if (slab_ != nullptr && slab_->Has(kSlabKindChunk, digest_hex))
    return slab_->ReadSlice(kSlabKindChunk, digest_hex, offset, len, dst);
  int fd = open(ChunkPath(digest_hex).c_str(), O_RDONLY);
  if (fd >= 0) {
    const bool ok = PreadFull(fd, dst, len, offset);
    close(fd);
    return ok;
  }
  // EC cold tier: positional reads are offset math over 1-2 data
  // shards (no decode on the healthy path).
  if (ec_ != nullptr && ec_->ReadChunkSlice(digest_hex, offset, len, dst))
    return true;
  // Released replica: fetch the WHOLE chunk from the group owner (the
  // wire round is per-chunk; slicing happens here) so the bytes can be
  // digest-verified before any of them reach the caller.
  if (remote_fetch_ != nullptr) {
    bool released = false;
    int64_t full_len = 0;
    {
      const Stripe& st = StripeFor(digest_hex);
      std::lock_guard<RankedMutex> lk(st.mu);
      if (st.released.count(digest_hex)) {
        released = true;
        auto l = st.lens.find(digest_hex);
        full_len = l != st.lens.end() ? l->second : 0;
      }
    }
    if (released && offset >= 0 && len >= 0 && offset + len <= full_len) {
      std::string buf;
      if (remote_fetch_(digest_hex, full_len, &buf) &&
          static_cast<int64_t>(buf.size()) == full_len &&
          Sha1(buf.data(), buf.size()).Hex() == digest_hex) {
        remote_reads_.fetch_add(1, std::memory_order_relaxed);
        memcpy(dst, buf.data() + offset, static_cast<size_t>(len));
        return true;
      }
    }
  }
  return false;
}

bool ChunkStore::ReadChunkSlices(const SliceReq* reqs, size_t n,
                                 int64_t* vec_batches, int64_t* vec_spans,
                                 std::string* failed) const {
  // Partition by residence: only slab-resident chunks can share a
  // preadv (flat chunks live one per inode, EC/released ones decode or
  // fetch).  Membership is probed lock-free like ReadChunkSlice; a
  // chunk that moves between the probe and the vectored read simply
  // falls back below.
  std::vector<SlabStore::SliceRead> slab_reqs;
  std::vector<size_t> slab_idx;
  for (size_t i = 0; i < n; ++i) {
    const SliceReq& r = reqs[i];
    if (slab_ != nullptr && slab_->Has(kSlabKindChunk, *r.digest_hex)) {
      slab_reqs.push_back(
          SlabStore::SliceRead{r.digest_hex, r.offset, r.len, r.dst});
      slab_idx.push_back(i);
    } else if (!ReadChunkSlice(*r.digest_hex, r.offset, r.len, r.dst)) {
      *failed = *r.digest_hex;
      return false;
    }
  }
  if (!slab_reqs.empty()) {
    std::unique_ptr<bool[]> ok(new bool[slab_reqs.size()]());
    slab_->ReadSlices(kSlabKindChunk, slab_reqs.data(), slab_reqs.size(),
                      ok.get(), vec_batches, vec_spans);
    for (size_t j = 0; j < slab_reqs.size(); ++j) {
      if (ok[j]) continue;
      // Raced a compaction (or the chunk left the slab): the full
      // fallthrough owns the retry.
      const SliceReq& r = reqs[slab_idx[j]];
      if (!ReadChunkSlice(*r.digest_hex, r.offset, r.len, r.dst)) {
        *failed = *r.digest_hex;
        return false;
      }
    }
  }
  return true;
}

// -- hot-chunk read cache -------------------------------------------------

std::shared_ptr<const std::string> ChunkStore::CacheGet(
    const std::string& digest_hex) {
  std::lock_guard<RankedMutex> lk(cache_.mu);
  auto it = cache_.index.find(digest_hex);
  if (it == cache_.index.end()) return nullptr;
  cache_.lru.splice(cache_.lru.begin(), cache_.lru, it->second);
  return it->second->data;
}

void ChunkStore::CacheInsertIfLive(const std::string& digest_hex,
                                   std::shared_ptr<const std::string> data) {
  if (data == nullptr ||
      static_cast<int64_t>(data->size()) > cache_.cap_bytes)
    return;
  // Re-check liveness UNDER the stripe lock: the disk read above ran
  // lock-free, so it may have raced a Quarantine() or a delete's
  // unlink.  Both invalidate under the stripe lock, so an insert gated
  // by the same lock can never publish a stale entry past them.
  Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> slk(st.mu);
  if (st.refs.find(digest_hex) == st.refs.end() ||
      st.quarantined.count(digest_hex))
    return;
  std::lock_guard<RankedMutex> lk(cache_.mu);
  if (cache_.index.count(digest_hex)) return;  // racer inserted first
  cache_.lru.push_front(CacheEntry{digest_hex, std::move(data)});
  cache_.index[digest_hex] = cache_.lru.begin();
  cache_.bytes += static_cast<int64_t>(cache_.lru.front().data->size());
  while (cache_.bytes > cache_.cap_bytes && !cache_.lru.empty()) {
    CacheEntry& victim = cache_.lru.back();
    cache_.bytes -= static_cast<int64_t>(victim.data->size());
    cache_.index.erase(victim.digest_hex);
    cache_.lru.pop_back();  // in-flight spans keep the bytes via shared_ptr
    cache_.evictions.fetch_add(1, std::memory_order_relaxed);
  }
}

void ChunkStore::CacheInvalidate(const std::string& digest_hex) {
  if (cache_.cap_bytes <= 0) return;
  std::lock_guard<RankedMutex> lk(cache_.mu);
  auto it = cache_.index.find(digest_hex);
  if (it == cache_.index.end()) return;
  cache_.bytes -= static_cast<int64_t>(it->second->data->size());
  cache_.lru.erase(it->second);
  cache_.index.erase(it);
  cache_.invalidations.fetch_add(1, std::memory_order_relaxed);
}

void ChunkStore::CacheClear() {
  std::lock_guard<RankedMutex> lk(cache_.mu);
  cache_.lru.clear();
  cache_.index.clear();
  cache_.bytes = 0;
}

std::shared_ptr<const std::string> ChunkStore::ReadChunkCached(
    const std::string& digest_hex, int64_t expect_len, bool* hit) {
  *hit = false;
  if (cache_.cap_bytes <= 0) return nullptr;
  auto p = CacheGet(digest_hex);
  if (p != nullptr && static_cast<int64_t>(p->size()) == expect_len) {
    *hit = true;
    cache_.hits.fetch_add(1, std::memory_order_relaxed);
    return p;
  }
  cache_.misses.fetch_add(1, std::memory_order_relaxed);
  auto fresh = std::make_shared<std::string>();
  if (!ReadChunk(digest_hex, expect_len, fresh.get())) return nullptr;
  std::shared_ptr<const std::string> frozen = std::move(fresh);
  CacheInsertIfLive(digest_hex, frozen);
  return frozen;
}

std::shared_ptr<const std::string> ChunkStore::CacheLookup(
    const std::string& digest_hex, int64_t expect_len) {
  if (cache_.cap_bytes <= 0) return nullptr;
  auto p = CacheGet(digest_hex);
  if (p != nullptr && static_cast<int64_t>(p->size()) == expect_len) {
    cache_.hits.fetch_add(1, std::memory_order_relaxed);
    return p;
  }
  return nullptr;
}

int64_t ChunkStore::cache_bytes() const {
  std::lock_guard<RankedMutex> lk(cache_.mu);
  return cache_.bytes;
}

int64_t ChunkStore::cache_chunks() const {
  std::lock_guard<RankedMutex> lk(cache_.mu);
  return static_cast<int64_t>(cache_.lru.size());
}

int64_t ChunkStore::unique_chunks() const {
  int64_t n = 0;
  for (const Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    n += static_cast<int64_t>(st.refs.size());
  }
  return n;
}

int64_t ChunkStore::gc_pending_chunks() const {
  int64_t n = 0;
  for (const Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    n += static_cast<int64_t>(st.zero_ref.size());
  }
  return n;
}

int64_t ChunkStore::quarantined_chunks() const {
  int64_t n = 0;
  for (const Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    n += static_cast<int64_t>(st.quarantined.size());
  }
  return n;
}

// -- integrity engine -----------------------------------------------------

std::vector<ChunkStore::ChunkInfo> ChunkStore::SnapshotLive(
    int prefix) const {
  static const char* kHex = "0123456789abcdef";
  char p0 = 0, p1 = 0;
  if (prefix >= 0) {
    p0 = kHex[(prefix >> 4) & 0xF];
    p1 = kHex[prefix & 0xF];
  }
  std::vector<ChunkInfo> out;
  // A byte prefix pins the stripe (stripe = high nibble), so a sliced
  // scan holds exactly one stripe lock; a full snapshot walks the 16
  // stripes one lock at a time (callers tolerate per-stripe tearing —
  // they already tolerated churn after a monolithic snapshot).
  int first = prefix >= 0 ? (prefix >> 4) & 0xF : 0;
  int last = prefix >= 0 ? first : kStripes - 1;
  for (int s = first; s <= last; ++s) {
    const Stripe& st = stripes_[s];
    std::lock_guard<RankedMutex> lk(st.mu);
    for (const auto& [dig, n] : st.refs) {
      if (prefix >= 0 && (dig[0] != p0 || dig[1] != p1)) continue;
      if (st.quarantined.count(dig)) continue;
      // Released chunks have no local bytes to verify — their integrity
      // lives with the group owner's stripe (EC repair stage).
      if (st.released.count(dig)) continue;
      auto l = st.lens.find(dig);
      out.push_back({dig, l != st.lens.end() ? l->second : 0});
    }
  }
  return out;
}

std::vector<ChunkStore::ChunkInfo> ChunkStore::SnapshotQuarantined() const {
  std::vector<ChunkInfo> out;
  for (const Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    for (const std::string& dig : st.quarantined) {
      if (st.refs.find(dig) == st.refs.end()) continue;  // zero-ref: GC's
      auto l = st.lens.find(dig);
      out.push_back({dig, l != st.lens.end() ? l->second : 0});
    }
  }
  return out;
}

bool ChunkStore::IsQuarantined(const std::string& digest_hex) const {
  const Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  return st.quarantined.count(digest_hex) != 0;
}

ChunkStore::QuarantineResult ChunkStore::Quarantine(
    const std::string& digest_hex) {
  Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  if (st.refs.find(digest_hex) == st.refs.end())
    return QuarantineResult::kGone;  // deleted since the snapshot
  if (st.pins.count(digest_hex)) return QuarantineResult::kPinned;
  // Slab-resident chunk: re-verify the record extent under the lock,
  // then preserve the bad bytes in quarantine/ (the flat path's rename
  // equivalent — forensics plus the heal/repair contract) and kill the
  // slot.  Compaction reclaims the dead extent later; the quarantine
  // mark is what routes re-uploads and replica repairs to the heal
  // path, exactly as for flat files.
  if (slab_ != nullptr && slab_->Has(kSlabKindChunk, digest_hex)) {
    std::string payload;
    bool readable = slab_->Read(kSlabKindChunk, digest_hex, &payload);
    if (readable && Sha1(payload.data(), payload.size()).Hex() == digest_hex)
      return QuarantineResult::kClean;
    mkdir((store_path_ + "/data/quarantine").c_str(), 0755);
    if (readable) {
      std::string werr;
      if (!WriteChunkFile(QuarantinePath(digest_hex), payload.data(),
                          payload.size(), &werr))
        FDFS_LOG_WARN("quarantine copy of slab chunk %s: %s",
                      digest_hex.c_str(), werr.c_str());
    }
    slab_->MarkDead(kSlabKindChunk, digest_hex);
    st.quarantined.insert(digest_hex);
    CacheInvalidate(digest_hex);
    return QuarantineResult::kQuarantined;
  }
  // Flat chunk: re-verify under the lock — the scrubber's verify read
  // ran lock-free, so it may have raced a delete + re-upload of this
  // digest and hashed a half-gone file.  No writer of this digest can
  // interleave with this read, so a clean hash here is authoritative.
  {
    int fd = open(ChunkPath(digest_hex).c_str(), O_RDONLY);
    if (fd >= 0) {
      Sha1Stream sha;
      char buf[65536];
      ssize_t r;
      while ((r = read(fd, buf, sizeof(buf))) > 0)
        sha.Update(buf, static_cast<size_t>(r));
      close(fd);
      if (r == 0 && sha.Final().Hex() == digest_hex)
        return QuarantineResult::kClean;
    }
  }
  mkdir((store_path_ + "/data/quarantine").c_str(), 0755);
  // A rename failure (e.g. the file already vanished) still marks the
  // chunk quarantined: either way the bytes are not servable, and the
  // mark is what routes re-uploads/repairs to the heal path.
  if (rename(ChunkPath(digest_hex).c_str(),
             QuarantinePath(digest_hex).c_str()) != 0 &&
      errno != ENOENT)
    FDFS_LOG_WARN("quarantine rename %s: %s", digest_hex.c_str(),
                  strerror(errno));
  st.quarantined.insert(digest_hex);
  // Same-lock cache invalidation: after this returns, no download can
  // serve the jailed bytes from the read cache (inserts re-check the
  // quarantine mark under this lock).
  CacheInvalidate(digest_hex);
  return QuarantineResult::kQuarantined;
}

bool ChunkStore::RepairChunk(const std::string& digest_hex, const char* data,
                             size_t len, std::string* err) {
  Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  if (st.refs.find(digest_hex) == st.refs.end()) {
    *err = "no longer referenced";
    return false;
  }
  if (!WriteChunkPayloadLocked(digest_hex, data, len, err)) return false;
  st.quarantined.erase(digest_hex);
  unlink(QuarantinePath(digest_hex).c_str());
  st.lens[digest_hex] = static_cast<int64_t>(len);
  // A repair RE-PROMOTES the chunk to the replicated tier: the local
  // payload is authoritative again, so any released mark clears and any
  // stale EC slot dies (the scrubber's kLost fallback routes here — the
  // stripe it came from is being dropped).
  if (st.released.count(digest_hex))
    UnreleaseLocked(st, digest_hex, static_cast<int64_t>(len));
  if (ec_ != nullptr) ec_->MarkDead(digest_hex, nullptr);
  // The repaired payload hashes to the digest, so a cached copy would
  // be byte-identical — but drop it anyway: the cache must never hold
  // an entry that predates a quarantine episode.
  CacheInvalidate(digest_hex);
  return true;
}

// -- erasure-coded cold tier ----------------------------------------------

void ChunkStore::AppendReleasedLog(const std::string& records) const {
  int fd = open(ReleasedLogPath().c_str(), O_CREAT | O_WRONLY | O_APPEND,
                0644);
  if (fd < 0) {
    FDFS_LOG_WARN("released.log open: %s", strerror(errno));
    return;
  }
  if (write(fd, records.data(), records.size()) !=
          static_cast<ssize_t>(records.size()) ||
      fsync(fd) != 0)
    FDFS_LOG_WARN("released.log append: %s", strerror(errno));
  close(fd);
}

void ChunkStore::UnreleaseLocked(Stripe& s, const std::string& digest_hex,
                                 int64_t len) {
  if (s.released.erase(digest_hex) == 0) return;
  released_chunks_--;
  released_bytes_ -= len;
  AppendReleasedLog("H " + digest_hex + "\n");
}

bool ChunkStore::IsReleased(const std::string& digest_hex) const {
  const Stripe& st = StripeFor(digest_hex);
  std::lock_guard<RankedMutex> lk(st.mu);
  return st.released.count(digest_hex) != 0;
}

std::vector<ChunkStore::ChunkInfo> ChunkStore::SnapshotDemotable(
    int64_t now_s, int64_t age_s) const {
  std::vector<ChunkInfo> out;
  if (ec_ == nullptr) return out;
  // Pass 1 (locked, per stripe): the cheap state filters.  The EC probe
  // runs under the stripe lock by rank (90 -> 96), and pins are the one
  // liveness signal demotion respects in advance — an EC-resident read
  // still serves pinned streams, but skipping hot pinned chunks avoids
  // demoting what a session is actively shipping.
  std::vector<ChunkInfo> candidates;
  for (const Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    for (const auto& [dig, n] : st.refs) {
      if (st.quarantined.count(dig) || st.released.count(dig) ||
          st.pins.count(dig))
        continue;
      if (ec_->Has(dig)) continue;
      auto l = st.lens.find(dig);
      candidates.push_back({dig, l != st.lens.end() ? l->second : 0});
    }
  }
  // Pass 2 (lock-free): coldness by payload mtime — flat file stat, or
  // the slab record's meta.  A chunk that vanished between the passes
  // simply fails both probes and drops out.
  for (ChunkInfo& c : candidates) {
    int64_t mtime = -1;
    if (slab_ != nullptr) {
      SlabStore::Slot slot;
      if (slab_->Lookup(kSlabKindChunk, c.digest_hex, &slot))
        mtime = slot.mtime;
    }
    if (mtime < 0) {
      struct stat fst;
      if (stat(ChunkPath(c.digest_hex).c_str(), &fst) == 0)
        mtime = static_cast<int64_t>(fst.st_mtime);
    }
    if (mtime >= 0 && now_s - mtime >= age_s)
      out.push_back(std::move(c));
  }
  return out;
}

int64_t ChunkStore::DemoteToEc(const std::vector<ChunkInfo>& chunks,
                               int64_t* chunks_demoted,
                               int64_t* bytes_demoted, std::string* err) {
  if (ec_ == nullptr) {
    *err = "ec tier disabled";
    return -1;
  }
  // Phase 1 (lock-free): read + SHA1-verify each candidate — the
  // stripe must never inherit bytes that would fail their own digest.
  std::vector<std::pair<std::string, std::string>> batch;
  for (const ChunkInfo& c : chunks) {
    std::string payload;
    if (!ReadChunk(c.digest_hex, c.length, &payload)) continue;
    if (Sha1(payload.data(), payload.size()).Hex() != c.digest_hex)
      continue;  // scrub's verify stage owns corruption; skip here
    if (ec_->Has(c.digest_hex)) continue;
    batch.emplace_back(c.digest_hex, std::move(payload));
  }
  if (batch.empty()) {
    *err = "no demotable chunks survived re-verify";
    return -1;
  }
  int64_t id = ec_->EncodeStripe(batch, err);
  if (id < 0) return -1;
  // Verify-then-release, local half: re-read the stripe from disk
  // through the decode path before ANY copy (local or replica) is
  // surrendered.
  if (!ec_->VerifyStripe(id, err)) {
    ec_->DropStripe(id, nullptr);
    return -1;
  }
  // Phase 2 (locked per digest): drop the local payload; refs/lens stay
  // and reads fall through to the stripe.  A digest deleted since phase
  // 1 has no refs — kill its freshly-encoded EC slot too, or the
  // content-addressed index would resurrect a deleted chunk.
  for (auto& [dig, payload] : batch) {
    Stripe& st = StripeFor(dig);
    std::lock_guard<RankedMutex> lk(st.mu);
    if (st.refs.find(dig) == st.refs.end()) {
      ec_->MarkDead(dig, nullptr);
      continue;
    }
    if (st.quarantined.count(dig)) continue;  // repair machinery owns it
    DropPayloadLocked(st, dig);
    if (chunks_demoted != nullptr) ++*chunks_demoted;
    if (bytes_demoted != nullptr)
      *bytes_demoted += static_cast<int64_t>(payload.size());
  }
  return id;
}

bool ChunkStore::PutSegmentStriped(const std::vector<SegmentChunk>& chunks,
                                   const ParityFn& encode, Recipe* recipe,
                                   StripeWrite* out, std::string* err) {
  if (ec_ == nullptr || ec_->k() <= 0) {
    *err = "ec tier disabled";
    return false;
  }
  // Phase 1: which chunks the store lacks (first sight in the segment
  // only).  Nothing is taken: a chunk another upload writes meanwhile is
  // then in two places, both its bytes.
  std::vector<bool> fresh(chunks.size(), false);
  std::vector<EcStore::StripeChunk> layout;
  int64_t data_len = 0;
  {
    std::unordered_set<std::string> seen;
    for (size_t i = 0; i < chunks.size(); ++i) {
      const std::string& dig = *chunks[i].digest_hex;
      if (!seen.insert(dig).second) continue;
      const Stripe& st = StripeFor(dig);
      std::lock_guard<RankedMutex> lk(st.mu);
      if (st.refs.count(dig) || st.zero_ref.count(dig)) continue;
      fresh[i] = true;
      layout.push_back({dig, chunks[i].length});
      data_len += chunks[i].length;
    }
  }
  // The stripe: the new chunks end to end in a buffer this thread keeps,
  // its parity from `encode` (the chip), or from the host where there is
  // no `encode` (cpu mode).
  if (!layout.empty()) {
    const int k = ec_->k(), m = ec_->m();
    const int64_t shard_len = EcStore::ShardLen(data_len, k);
    thread_local std::string region;
    region.assign(static_cast<size_t>(k * shard_len), '\0');
    int64_t off = 0;
    for (size_t i = 0; i < chunks.size(); ++i) {
      if (!fresh[i]) continue;
      memcpy(region.data() + off, chunks[i].data,
             static_cast<size_t>(chunks[i].length));
      off += chunks[i].length;
    }
    std::string parity;
    if (encode != nullptr &&
        !encode(k, m, shard_len, region.data(), &parity)) {
      *err = "stripe parity unavailable";
      out->encode_failed = true;
      return false;
    }
    if (ec_->CommitStripe(layout, region.data(),
                          encode != nullptr ? parity.data() : nullptr,
                          err) < 0)
      return false;
    out->stripe_bytes = k * shard_len;
  }
  // Phase 2: the references, in the segment's order.
  for (size_t i = 0; i < chunks.size(); ++i) {
    const SegmentChunk& c = chunks[i];
    const std::string& dig = *c.digest_hex;
    bool existed = false;
    if (!fresh[i]) {
      if (!PutAndRef(dig, c.data, static_cast<size_t>(c.length), &existed,
                     err))
        return false;
    } else {
      Stripe& st = StripeFor(dig);
      std::lock_guard<RankedMutex> lk(st.mu);
      auto it = st.refs.find(dig);
      auto z = st.zero_ref.find(dig);
      if (it != st.refs.end()) {
        it->second++;  // another upload referenced it first
        existed = true;
      } else if (z != st.zero_ref.end()) {
        st.refs[dig] = 1;
        st.lens[dig] = z->second.length;
        unique_bytes_ += z->second.length;
        zero_ref_bytes_ -= z->second.length;
        st.zero_ref.erase(z);
        existed = true;
      } else {
        // A delete of another file that held the digest can have marked
        // the new slot dead since the commit: then the bytes land flat.
        if (!ec_->Has(dig) &&
            !WriteChunkPayloadLocked(dig, c.data,
                                     static_cast<size_t>(c.length), err))
          return false;
        st.refs[dig] = 1;
        st.lens[dig] = c.length;
        unique_bytes_ += c.length;
      }
    }
    if (existed) {
      ++out->chunk_hits;
      out->saved_bytes += c.length;
    } else {
      ++out->chunk_misses;
    }
    recipe->chunks.push_back({dig, c.length});
  }
  return true;
}

std::string ChunkStore::ReleaseChunks(const std::vector<ChunkInfo>& chunks) {
  std::string kept(chunks.size(), '\0');
  std::string journal;
  for (size_t i = 0; i < chunks.size(); ++i) {
    const std::string& dig = chunks[i].digest_hex;
    Stripe& st = StripeFor(dig);
    std::lock_guard<RankedMutex> lk(st.mu);
    auto it = st.refs.find(dig);
    if (it == st.refs.end()) continue;      // never held: nothing retained
    if (st.released.count(dig)) continue;   // idempotent replay
    if (st.pins.count(dig) || st.quarantined.count(dig)) {
      // An in-flight stream still reads the local bytes, or the
      // quarantine/repair lifecycle owns them — keep the replica; the
      // owner keeps full-copy coverage for this digest and may retry
      // next pass.
      kept[i] = 1;
      continue;
    }
    DropPayloadLocked(st, dig);
    st.released.insert(dig);
    released_chunks_++;
    released_bytes_ += chunks[i].length;
    journal += "R " + dig + " " + std::to_string(chunks[i].length) + "\n";
  }
  // One durable append for the whole batch BEFORE the response: the
  // owner treats a 0 byte as permission to count this replica gone, so
  // the mark must survive a crash (or a restart would serve the digest
  // as locally-missing instead of remote-fetching).
  if (!journal.empty()) AppendReleasedLog(journal);
  return kept;
}

// -- recipe sidecars (slab-aware) -----------------------------------------

std::string ChunkStore::RecipeSlabKey(const std::string& rcp_path) const {
  // Keys are store-root-relative so replicas (different absolute roots)
  // and relocated stores derive identical keys from identical layouts.
  if (rcp_path.compare(0, store_path_.size(), store_path_) == 0) {
    size_t start = store_path_.size();
    while (start < rcp_path.size() && rcp_path[start] == '/') ++start;
    return rcp_path.substr(start);
  }
  return rcp_path;
}

bool ChunkStore::StoreRecipe(const std::string& rcp_path, const Recipe& r,
                             std::string* err) {
  std::string key = RecipeSlabKey(rcp_path);
  // Size-probe arithmetically (24B header + 28B/chunk) so a recipe that
  // stays flat — every file past ~19 MB at default thresholds — is not
  // encoded twice on the upload hot path.
  int64_t encoded_size = 24 + 28 * static_cast<int64_t>(r.chunks.size());
  if (slab_ != nullptr && slab_opts_.recipe_threshold > 0 &&
      key.size() <= kSlabKeyMaxLen &&
      encoded_size < slab_opts_.recipe_threshold) {
    std::string buf = EncodeRecipe(r);
    // durable: recipes keep WriteRecipeFile's fsync guarantee — the
    // recipe IS the file's existence, chunks are resurrectable.
    if (!slab_->Append(kSlabKindRecipe, key, buf.data(), buf.size(),
                       /*durable=*/true, err))
      return false;
    // A flat sidecar from before a threshold change must not shadow
    // (or double-count refs for) the slab record.
    unlink(rcp_path.c_str());
    return true;
  }
  // Flat sidecar: the recipe is the only thing that needs the file-id
  // directory fan-out, so the dirs are created HERE, not by callers — a
  // slab-resident recipe must cost zero inodes, fan-out dirs included
  // (they dominate the inode bill on small-file corpora otherwise).
  EnsureParentDirs(rcp_path);
  if (!WriteRecipeFile(rcp_path, r, err)) return false;
  if (slab_ != nullptr) slab_->MarkDead(kSlabKindRecipe, key);
  return true;
}

std::optional<Recipe> ChunkStore::LoadRecipe(
    const std::string& rcp_path) const {
  if (slab_ != nullptr) {
    std::string payload;
    if (slab_->Read(kSlabKindRecipe, RecipeSlabKey(rcp_path), &payload))
      return DecodeRecipe(payload.data(), payload.size());
  }
  return ReadRecipeFile(rcp_path);
}

bool ChunkStore::HasRecipe(const std::string& rcp_path) const {
  if (slab_ != nullptr &&
      slab_->Has(kSlabKindRecipe, RecipeSlabKey(rcp_path)))
    return true;
  struct stat st;
  return stat(rcp_path.c_str(), &st) == 0;
}

bool ChunkStore::RemoveRecipe(const std::string& rcp_path,
                              int64_t* bytes_out) {
  bool found = false;
  int64_t bytes = 0;
  if (slab_ != nullptr) {
    int64_t payload_len = 0;
    if (slab_->MarkDead(kSlabKindRecipe, RecipeSlabKey(rcp_path),
                        &payload_len)) {
      found = true;
      bytes += payload_len;
    }
  }
  struct stat st;
  if (stat(rcp_path.c_str(), &st) == 0 && unlink(rcp_path.c_str()) == 0) {
    found = true;
    bytes += st.st_size;
  }
  if (bytes_out != nullptr) *bytes_out = bytes;
  return found;
}

int64_t ChunkStore::CompactSlabs(const std::function<void(int64_t)>& pace,
                                 const std::function<bool()>& stop,
                                 std::vector<ChunkInfo>* corrupt,
                                 int64_t* reclaimed) {
  if (slab_ == nullptr) return 0;
  SlabStore::CompactResult res = slab_->Compact(pace, stop);
  if (reclaimed != nullptr) *reclaimed += res.reclaimed_bytes;
  // Copy-time re-verify failures ride the standard quarantine/heal
  // machinery: the caller (scrub pass) runs HandleCorrupt on each,
  // which quarantines the slot (marking it dead — letting the next
  // compaction finish the slab) and repairs from a group replica.
  if (corrupt != nullptr) {
    for (const std::string& dig : res.corrupt_chunk_keys) {
      int64_t len = 0;
      {
        const Stripe& st = StripeFor(dig);
        std::lock_guard<RankedMutex> lk(st.mu);
        auto it = st.lens.find(dig);
        if (it != st.lens.end()) len = it->second;
      }
      corrupt->push_back({dig, len});
    }
  }
  for (const std::string& key : res.corrupt_recipe_keys) {
    // Preserve the bytes for forensics, then KILL the slot: a live
    // corrupt recipe would keep HasRecipe() true, which blocks the
    // idempotent sync-replay re-store and recovery's resume check —
    // the file would stay unreadable forever despite healthy replicas,
    // and its slab could never finish compacting.  Dead, the name
    // reads as absent and replica re-sync/recovery recreates it.
    std::string payload, werr;
    if (slab_->Read(kSlabKindRecipe, key, &payload)) {
      mkdir((store_path_ + "/data/quarantine").c_str(), 0755);
      std::string qname = key;
      for (char& c : qname)
        if (c == '/') c = '_';
      if (!WriteChunkFile(store_path_ + "/data/quarantine/recipe_" + qname,
                          payload.data(), payload.size(), &werr))
        FDFS_LOG_WARN("slab compact: quarantine copy of recipe %s: %s",
                      key.c_str(), werr.c_str());
    }
    slab_->MarkDead(kSlabKindRecipe, key);
    FDFS_LOG_ERROR("slab compact: recipe record %s failed re-verify — "
                   "slot killed (bytes preserved under data/quarantine/); "
                   "replica re-sync/recovery recreates the file",
                   key.c_str());
    if (events_ != nullptr)
      events_->Record(EventSeverity::kError, "slab.recipe_corrupt", key,
                      "bytes=" + std::to_string(payload.size()));
  }
  if (events_ != nullptr && res.slabs_compacted > 0)
    events_->Record(EventSeverity::kInfo, "slab.compact", store_path_,
                    "slabs=" + std::to_string(res.slabs_compacted) +
                        " reclaimed_bytes=" +
                        std::to_string(res.reclaimed_bytes) +
                        " copied=" + std::to_string(res.copied_records));
  return res.slabs_compacted;
}

int64_t ChunkStore::GcSweep(int64_t now_s, int64_t* bytes) {
  int64_t reclaimed = 0;
  for (Stripe& st : stripes_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    for (auto it = st.zero_ref.begin(); it != st.zero_ref.end();) {
      if (now_s - it->second.since_s < gc_grace_s_ ||
          st.pins.count(it->first)) {
        // Inside the grace window, or pinned by an in-flight stream /
        // phase-1 upload session — the pin probe shares this stripe
        // lock with the unlink, so PinAndMask can never lose the race.
        ++it;
        continue;
      }
      UnlinkRetiredLocked(st, it->first);
      zero_ref_bytes_ -= it->second.length;
      *bytes += it->second.length;
      ++reclaimed;
      it = st.zero_ref.erase(it);
    }
  }
  return reclaimed;
}

namespace {

void WalkRecipes(const std::string& dir,
                 const std::function<bool(const std::string&)>& skip_flat,
                 std::unordered_map<std::string, int64_t>* refs,
                 std::unordered_map<std::string, int64_t>* lens) {
  DIR* d = opendir(dir.c_str());
  if (d == nullptr) return;
  struct dirent* de;
  while ((de = readdir(d)) != nullptr) {
    std::string name = de->d_name;
    if (name == "." || name == "..") continue;
    std::string path = dir + "/" + name;
    struct stat st;
    if (stat(path.c_str(), &st) != 0) continue;
    if (S_ISDIR(st.st_mode)) {
      if (name != "chunks" && name != "sync" && name != "tmp" &&
          name != "slabs" && name != "ec")
        WalkRecipes(path, skip_flat, refs, lens);
    } else if (name.size() > 4 &&
               name.compare(name.size() - 4, 4, ".rcp") == 0) {
      if (skip_flat != nullptr && skip_flat(path)) continue;
      auto r = ReadRecipeFile(path);
      if (!r.has_value()) {
        FDFS_LOG_WARN("unreadable recipe %s ignored", path.c_str());
        continue;
      }
      for (const RecipeEntry& e : r->chunks) {
        (*refs)[e.digest_hex]++;
        (*lens)[e.digest_hex] = e.length;
      }
    }
  }
  closedir(d);
}

}  // namespace

void ChunkStore::RebuildFromRecipes() {
  // Slab slot index first: recipes may live there, and the orphan scan
  // below needs the chunk records indexed.  Same no-binlog philosophy —
  // the slab headers on disk are the ground truth.
  if (slab_ != nullptr) slab_->ScanRebuild();
  // EC stripe manifests next (same ground-truth philosophy; also
  // collects orphan shards from crashed encodes).
  if (ec_ != nullptr) ec_->Rescan();

  std::unordered_map<std::string, int64_t> refs, lens;
  // Cross-layout dedup: a crash inside StoreRecipe (between the slab
  // append and the flat-twin unlink, or vice versa) can leave BOTH
  // representations of one recipe on disk.  They encode the identical
  // Recipe (one StoreRecipe call wrote both), so count refs from the
  // slab copy only and drop the flat twin — double-counting would pin
  // the file's chunks with refs that no single delete can release.
  auto skip_flat = [this](const std::string& rcp_path) {
    if (slab_ == nullptr ||
        !slab_->Has(kSlabKindRecipe, RecipeSlabKey(rcp_path)))
      return false;
    FDFS_LOG_INFO("recipe %s exists in both layouts (crash window): "
                  "keeping the slab record, dropping the flat twin",
                  rcp_path.c_str());
    unlink(rcp_path.c_str());
    return true;
  };
  WalkRecipes(store_path_ + "/data", skip_flat, &refs, &lens);
  if (slab_ != nullptr) {
    slab_->ForEachLive(
        kSlabKindRecipe,
        [&](const std::string& key, const std::string& payload) {
          auto r = DecodeRecipe(payload.data(), payload.size());
          if (!r.has_value()) {
            FDFS_LOG_WARN("unreadable slab recipe %s ignored", key.c_str());
            return;
          }
          for (const RecipeEntry& e : r->chunks) {
            refs[e.digest_hex]++;
            lens[e.digest_hex] = e.length;
          }
        });
  }

  // GC pass: any chunk file not named by a recipe is an orphan — a
  // crash leftover, or (with a GC grace window) a deliberately-retired
  // zero-ref chunk whose grace had not expired at shutdown.  Eager mode
  // drops orphans on the spot (the original behavior); grace mode
  // parks them in zero_ref aged by file mtime, so the grace window is
  // crash-safe instead of resetting on every restart.
  int64_t orphans = 0, parked = 0, bytes = 0;
  std::unordered_map<std::string, ZeroRef> zero;
  std::string croot = store_path_ + "/data/chunks";
  DIR* d1 = opendir(croot.c_str());
  if (d1 != nullptr) {
    struct dirent* e1;
    while ((e1 = readdir(d1)) != nullptr) {
      if (e1->d_name[0] == '.') continue;
      std::string l1 = croot + "/" + e1->d_name;
      DIR* d2 = opendir(l1.c_str());
      if (d2 == nullptr) continue;
      struct dirent* e2;
      while ((e2 = readdir(d2)) != nullptr) {
        if (e2->d_name[0] == '.') continue;
        std::string l2 = l1 + "/" + e2->d_name;
        DIR* d3 = opendir(l2.c_str());
        if (d3 == nullptr) continue;
        struct dirent* e3;
        while ((e3 = readdir(d3)) != nullptr) {
          std::string name = e3->d_name;
          if (name[0] == '.') continue;
          if (IsHex40(name) && refs.find(name) != refs.end()) continue;
          std::string path = l2 + "/" + name;
          struct stat st;
          if (IsHex40(name) && gc_grace_s_ > 0 &&
              stat(path.c_str(), &st) == 0) {
            zero[name] = ZeroRef{static_cast<int64_t>(st.st_size),
                                 static_cast<int64_t>(st.st_mtime)};
            lens[name] = static_cast<int64_t>(st.st_size);
            ++parked;
          } else {
            unlink(path.c_str());
            ++orphans;
          }
        }
        closedir(d3);
      }
      closedir(d2);
    }
    closedir(d1);
  }
  // Slab-resident orphans: live chunk records no recipe names.  Grace
  // mode parks them (aged by the record's mtime, so the window is
  // crash-safe like the flat path's file-mtime aging); eager mode marks
  // the slots dead on the spot.
  if (slab_ != nullptr) {
    std::vector<std::string> dead;
    slab_->ForEachLiveMeta(
        kSlabKindChunk, [&](const SlabStore::RecordMeta& m) {
          if (refs.find(m.key) != refs.end()) {
            lens.emplace(m.key, m.payload_len);
            return;
          }
          if (gc_grace_s_ > 0) {
            zero[m.key] = ZeroRef{m.payload_len,
                                  m.mtime > 0 ? m.mtime : time(nullptr)};
            lens[m.key] = m.payload_len;
            ++parked;
          } else {
            dead.push_back(m.key);
            ++orphans;
          }
        });
    for (const std::string& key : dead)
      slab_->MarkDead(kSlabKindChunk, key);
  }

  // Quarantine survives restarts: a referenced digest whose bytes sit in
  // quarantine/ must keep reading as missing (and healable), or a
  // restart would silently re-admit the corrupt state.  Unreferenced
  // quarantine files are corrupt garbage nobody names — drop them.
  std::unordered_set<std::string> quarantined;
  std::string qroot = store_path_ + "/data/quarantine";
  DIR* qd = opendir(qroot.c_str());
  if (qd != nullptr) {
    struct dirent* qe;
    while ((qe = readdir(qd)) != nullptr) {
      std::string name = qe->d_name;
      if (name[0] == '.') continue;
      // Forensic copies of corrupt slab RECIPES (CompactSlabs) keep
      // their bytes across restarts — the operator drains them by hand
      // like chunk quarantine files.
      if (name.compare(0, 7, "recipe_") == 0) continue;
      if (IsHex40(name) && refs.find(name) != refs.end()) {
        struct stat st;
        if (stat(ChunkPath(name).c_str(), &st) == 0 ||
            (slab_ != nullptr && slab_->Has(kSlabKindChunk, name))) {
          // A healed copy already lives in chunks/ or the slab store
          // (crash between the repair write and the quarantine
          // unlink): prefer it.
          unlink((qroot + "/" + name).c_str());
        } else {
          quarantined.insert(name);
        }
      } else {
        unlink((qroot + "/" + name).c_str());
      }
    }
    closedir(qd);
  }

  // Distribute the rebuilt maps into their stripes.  Startup runs
  // before serving, but take the locks anyway — Rebuild is also called
  // in tests against a store that already served.
  size_t unique = 0;
  int64_t ub = 0, zb = 0;
  std::array<Stripe, kStripes> fresh;
  for (auto& [dig, n] : refs) {
    Stripe& st = fresh[StripeIndex(dig)];
    st.refs[dig] = n;
  }
  for (auto& [dig, l] : lens) fresh[StripeIndex(dig)].lens[dig] = l;
  for (auto& [dig, z] : zero) {
    fresh[StripeIndex(dig)].zero_ref[dig] = z;
    zb += z.length;
  }
  for (auto& dig : quarantined) fresh[StripeIndex(dig)].quarantined.insert(dig);
  for (const auto& [dig, n] : refs) ub += lens[dig];
  unique = refs.size();
  for (int s = 0; s < kStripes; ++s) {
    Stripe& st = stripes_[s];
    std::lock_guard<RankedMutex> lk(st.mu);
    st.refs = std::move(fresh[s].refs);
    st.lens = std::move(fresh[s].lens);
    st.zero_ref = std::move(fresh[s].zero_ref);
    st.quarantined = std::move(fresh[s].quarantined);
    st.pins.clear();
    st.released.clear();  // re-derived from released.log below
  }
  unique_bytes_ = ub;
  zero_ref_bytes_ = zb;
  bytes = ub;
  // released.log replay: re-mark replicas this node surrendered via
  // EC_RELEASE.  A mark survives only while it is still true — the
  // digest must be referenced and genuinely payload-less locally (a
  // heal that crashed before its 'H' append shows up as bytes on disk
  // and wins).  The journal is rewritten compacted with the surviving
  // set, so it never grows unboundedly across release/heal churn.
  released_chunks_ = 0;
  released_bytes_ = 0;
  {
    std::unordered_map<std::string, int64_t> marks;
    std::string jbuf;
    if (ReadWholeFile(ReleasedLogPath(), &jbuf)) {
      size_t pos = 0;
      while (pos < jbuf.size()) {
        size_t eol = jbuf.find('\n', pos);
        if (eol == std::string::npos) eol = jbuf.size();
        std::string line = jbuf.substr(pos, eol - pos);
        pos = eol + 1;
        if (line.size() >= 42 && line[1] == ' ' &&
            IsHex40(line.substr(2, 40))) {
          if (line[0] == 'R')
            marks[line.substr(2, 40)] =
                strtoll(line.c_str() + 42, nullptr, 10);
          else if (line[0] == 'H')
            marks.erase(line.substr(2, 40));
        }
      }
    }
    std::string compacted;
    for (const auto& [dig, mlen] : marks) {
      Stripe& st = stripes_[StripeIndex(dig)];
      std::lock_guard<RankedMutex> lk(st.mu);
      if (st.refs.find(dig) == st.refs.end()) continue;  // deleted
      struct stat fst;
      if (stat(ChunkPath(dig).c_str(), &fst) == 0 ||
          (slab_ != nullptr && slab_->Has(kSlabKindChunk, dig)))
        continue;  // bytes came back (heal crashed pre-'H'): not released
      st.released.insert(dig);
      int64_t l = mlen;
      auto li = st.lens.find(dig);
      if (li != st.lens.end()) l = li->second;
      released_chunks_++;
      released_bytes_ += l;
      compacted += "R " + dig + " " + std::to_string(l) + "\n";
    }
    if (marks.empty() && compacted.empty()) {
      unlink(ReleasedLogPath().c_str());
    } else {
      std::string tmp = ReleasedLogPath() + ".tmp";
      std::string werr;
      if (WriteChunkFile(tmp, compacted.data(), compacted.size(), &werr)) {
        if (rename(tmp.c_str(), ReleasedLogPath().c_str()) != 0)
          FDFS_LOG_WARN("released.log rewrite: %s", strerror(errno));
      } else {
        FDFS_LOG_WARN("released.log rewrite: %s", werr.c_str());
      }
    }
  }
  CacheClear();
  if (unique > 0 || orphans > 0 || parked > 0 || !quarantined.empty())
    FDFS_LOG_INFO("chunk store: %zu unique chunks (%lld bytes), %lld "
                  "orphans collected, %lld awaiting GC, %zu quarantined",
                  unique, static_cast<long long>(bytes),
                  static_cast<long long>(orphans),
                  static_cast<long long>(parked), quarantined.size());
}

}  // namespace fdfs
