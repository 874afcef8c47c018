#include "storage/slabstore.h"

#include <dirent.h>
#include <fcntl.h>
#include <string.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstdio>
#include <ctime>
#include <set>

#include "common/bytes.h"
#include "common/fsutil.h"
#include "common/log.h"

namespace fdfs {

namespace {

constexpr char kSlabMagic[4] = {'F', 'S', 'L', 'B'};
constexpr uint8_t kSlabVersion = 1;
constexpr uint8_t kSlabFlagDead = 0x01;
constexpr int kFlagsOffset = 6;

int64_t RecordExtent(size_t key_len, int64_t alloc_len) {
  return static_cast<int64_t>(kSlabRecordHeaderSize + key_len) + alloc_len;
}

// Vectored read of the full iov chain at offset; false on any short
// read or error.  Advances through partial reads like PreadFull.
bool PreadvAll(int fd, struct iovec* iov, int iovcnt, int64_t offset) {
  while (iovcnt > 0) {
    ssize_t r = preadv(fd, iov, iovcnt, offset);
    if (r <= 0) return false;
    offset += r;
    while (r > 0 && iovcnt > 0) {
      if (static_cast<size_t>(r) >= iov->iov_len) {
        r -= static_cast<ssize_t>(iov->iov_len);
        ++iov;
        --iovcnt;
      } else {
        iov->iov_base = static_cast<char*>(iov->iov_base) + r;
        iov->iov_len -= static_cast<size_t>(r);
        r = 0;
      }
    }
  }
  return true;
}

bool WriteAll(int fd, const char* data, size_t len, int64_t offset) {
  size_t off = 0;
  while (off < len) {
    ssize_t w = pwrite(fd, data + off, len - off,
                       offset + static_cast<int64_t>(off));
    if (w <= 0) return false;
    off += static_cast<size_t>(w);
  }
  return true;
}

}  // namespace

std::string SlabEncodeRecord(uint8_t kind, const std::string& key,
                             const char* data, size_t len, int64_t mtime) {
  std::string rec;
  rec.reserve(kSlabRecordHeaderSize + key.size() + len);
  rec.append(kSlabMagic, sizeof(kSlabMagic));
  rec.push_back(static_cast<char>(kSlabVersion));
  rec.push_back(static_cast<char>(kind));
  rec.push_back('\0');  // flags (live); zeroed in the header CRC anyway
  rec.push_back(static_cast<char>(key.size()));
  uint8_t num[8];
  PutInt64BE(static_cast<int64_t>(len), num);  // alloc == payload today
  rec.append(reinterpret_cast<char*>(num), 8);
  PutInt64BE(static_cast<int64_t>(len), num);
  rec.append(reinterpret_cast<char*>(num), 8);
  uint8_t crc[4];
  PutInt32BE(Crc32(data, len), crc);
  rec.append(reinterpret_cast<char*>(crc), 4);
  PutInt64BE(mtime, num);
  rec.append(reinterpret_cast<char*>(num), 8);
  PutInt32BE(Crc32(rec.data(), 36), crc);
  rec.append(reinterpret_cast<char*>(crc), 4);
  rec.append(key);
  rec.append(data, len);
  return rec;
}

bool SlabDecodeRecord(const char* p, size_t avail, SlabRecordView* out) {
  if (avail < kSlabRecordHeaderSize) return false;
  if (memcmp(p, kSlabMagic, sizeof(kSlabMagic)) != 0) return false;
  const uint8_t* u = reinterpret_cast<const uint8_t*>(p);
  if (u[4] != kSlabVersion) return false;
  uint8_t kind = u[5];
  if (kind != kSlabKindChunk && kind != kSlabKindRecipe) return false;
  uint8_t flags = u[6];
  size_t key_len = u[7];
  int64_t alloc_len = GetInt64BE(u + 8);
  int64_t payload_len = GetInt64BE(u + 16);
  if (key_len == 0 || alloc_len < 0 || payload_len < 0 ||
      payload_len > alloc_len)
    return false;
  // Header CRC covers bytes [0,36) with the flags byte zeroed, so the
  // in-place dead mark never invalidates it.
  uint8_t hdr[36];
  memcpy(hdr, p, 36);
  hdr[kFlagsOffset] = 0;
  if (Crc32(hdr, 36) != GetInt32BE(u + 36)) return false;
  if (avail < kSlabRecordHeaderSize + key_len) return false;
  out->kind = kind;
  out->flags = flags;
  out->key.assign(p + kSlabRecordHeaderSize, key_len);
  out->alloc_len = alloc_len;
  out->payload_len = payload_len;
  out->payload_crc32 = GetInt32BE(u + 24);
  out->mtime = GetInt64BE(u + 28);
  out->record_len = RecordExtent(key_len, alloc_len);
  return true;
}

SlabStore::SlabStore(std::string dir, int64_t slab_bytes, int min_dead_pct)
    : dir_(std::move(dir)),
      slab_bytes_(slab_bytes < (1 << 20) ? (1 << 20) : slab_bytes),
      min_dead_pct_(min_dead_pct < 1 ? 1 : (min_dead_pct > 100 ? 100
                                                               : min_dead_pct)) {
  for (int i = 0; i < kIndexStripes; ++i) index_[i].mu.set_order_key(i);
}

SlabStore::~SlabStore() {
  if (active_fd_ >= 0) close(active_fd_);
  if (flag_fd_ >= 0) close(flag_fd_);
}

int SlabStore::StripeFor(const std::string& ikey) const {
  return static_cast<int>(std::hash<std::string>{}(ikey) %
                          static_cast<size_t>(kIndexStripes));
}

std::string SlabStore::SlabPath(int64_t slab_id) const {
  char name[32];
  snprintf(name, sizeof(name), "%010lld.slab",
           static_cast<long long>(slab_id));
  return dir_ + "/" + name;
}

void SlabStore::FlagDeadOnDisk(int64_t slab_id, int64_t record_off) const {
  // mu_ held (every call site).  The fd is cached per slab — see the
  // member comment.
  if (flag_fd_ >= 0 && flag_fd_slab_ != slab_id) {
    close(flag_fd_);
    flag_fd_ = -1;
  }
  if (flag_fd_ < 0) {
    flag_fd_ = open(SlabPath(slab_id).c_str(), O_WRONLY);
    if (flag_fd_ < 0) return;  // best-effort: RAM accounting rules
    flag_fd_slab_ = slab_id;
  }
  char dead = static_cast<char>(kSlabFlagDead);
  if (pwrite(flag_fd_, &dead, 1, record_off + kFlagsOffset) != 1)
    FDFS_LOG_WARN("slab %lld: dead-flag write at %lld failed: %s",
                  static_cast<long long>(slab_id),
                  static_cast<long long>(record_off), strerror(errno));
}

void SlabStore::AccountDeadLocked(int64_t slab_id, int64_t record_extent) {
  auto it = slabs_.find(slab_id);
  if (it != slabs_.end()) {
    it->second.live_slots--;
    it->second.dead_slots++;
    it->second.live_bytes -= record_extent;
    it->second.dead_bytes += record_extent;
  }
  slots_live_.fetch_sub(1, std::memory_order_relaxed);
  slots_dead_.fetch_add(1, std::memory_order_relaxed);
  bytes_live_.fetch_sub(record_extent, std::memory_order_relaxed);
  bytes_dead_.fetch_add(record_extent, std::memory_order_relaxed);
}

bool SlabStore::EnsureActiveLocked(int64_t need, std::string* err) {
  if (active_fd_ >= 0 && active_size_ >= slab_bytes_) {
    close(active_fd_);
    active_fd_ = -1;
  }
  if (active_fd_ < 0) {
    if (active_id_ == 0) {
      // First append of this process with no scan: start after the
      // highest existing slab (ScanRebuild normally sets this).
      active_id_ = 1;
      for (const auto& [id, info] : slabs_)
        if (id >= active_id_) active_id_ = id + 1;
    } else if (active_size_ >= slab_bytes_) {
      active_id_++;
    }
    // First append may precede any other write under the store root:
    // create the parent chain (…/data, then …/data/slabs).
    size_t slash = dir_.rfind('/');
    if (slash != std::string::npos)
      mkdir(dir_.substr(0, slash).c_str(), 0755);
    mkdir(dir_.c_str(), 0755);
    std::string path = SlabPath(active_id_);
    active_fd_ = open(path.c_str(), O_CREAT | O_WRONLY, 0644);
    if (active_fd_ < 0) {
      *err = "open " + path + ": " + strerror(errno);
      return false;
    }
    struct stat st;
    active_size_ = fstat(active_fd_, &st) == 0 ? st.st_size : 0;
    slabs_.emplace(active_id_, SlabInfo{});
    files_.store(static_cast<int64_t>(slabs_.size()),
                 std::memory_order_relaxed);
    auto& info = slabs_[active_id_];
    if (info.size_bytes < active_size_) info.size_bytes = active_size_;
  }
  (void)need;
  return true;
}

bool SlabStore::AppendInternal(uint8_t kind, const std::string& key,
                               const char* data, size_t len, bool durable,
                               const Slot* expect_old, std::string* err) {
  if (key.empty() || key.size() > kSlabKeyMaxLen) {
    *err = "slab key length " + std::to_string(key.size()) +
           " out of range";
    return false;
  }
  int64_t now = time(nullptr);
  std::string rec = SlabEncodeRecord(kind, key, data, len, now);
  Slot fresh;
  fresh.mtime = now;
  {
    std::lock_guard<RankedMutex> lk(mu_);
    if (!EnsureActiveLocked(static_cast<int64_t>(rec.size()), err))
      return false;
    int64_t off = active_size_;
    if (!WriteAll(active_fd_, rec.data(), rec.size(), off)) {
      *err = "append " + SlabPath(active_id_) + ": " + strerror(errno);
      // Trim any partial tail so a later append never leaves a torn
      // record in the middle of the file.
      if (ftruncate(active_fd_, off) != 0)
        FDFS_LOG_WARN("slab %lld: truncate after failed append: %s",
                      static_cast<long long>(active_id_), strerror(errno));
      return false;
    }
    if (durable && fsync(active_fd_) != 0) {
      *err = "fsync " + SlabPath(active_id_) + ": " + strerror(errno);
      if (ftruncate(active_fd_, off) != 0)
        FDFS_LOG_WARN("slab %lld: truncate after failed fsync: %s",
                      static_cast<long long>(active_id_), strerror(errno));
      return false;
    }
    active_size_ = off + static_cast<int64_t>(rec.size());
    fresh.slab_id = active_id_;
    fresh.record_off = off;
    fresh.payload_off = off + static_cast<int64_t>(kSlabRecordHeaderSize +
                                                   key.size());
    fresh.payload_len = static_cast<int64_t>(len);
    int64_t extent = static_cast<int64_t>(rec.size());
    auto& info = slabs_[active_id_];
    info.size_bytes = active_size_;
    info.live_slots++;
    info.live_bytes += extent;
    slots_live_.fetch_add(1, std::memory_order_relaxed);
    bytes_live_.fetch_add(extent, std::memory_order_relaxed);

    // Publish under the index stripe (mu_ still held: rank 92 -> 94,
    // and the dead-accounting of a replaced entry needs mu_ anyway).
    std::string ikey = IndexKey(kind, key);
    IndexStripe& st = index_[StripeFor(ikey)];
    std::lock_guard<RankedMutex> ilk(st.mu);
    auto it = st.map.find(ikey);
    if (expect_old != nullptr &&
        (it == st.map.end() || it->second.slab_id != expect_old->slab_id ||
         it->second.record_off != expect_old->record_off)) {
      // Compaction raced a delete or a replace of this key: the copy we
      // just appended is already stale — mark it dead, keep the index
      // as the racer left it.
      AccountDeadLocked(fresh.slab_id, extent);
      FlagDeadOnDisk(fresh.slab_id, fresh.record_off);
      return true;
    }
    if (it != st.map.end()) {
      // Replace semantics: the old record dies in place.
      Slot old = it->second;
      AccountDeadLocked(old.slab_id,
                        RecordExtent(key.size(), old.payload_len));
      FlagDeadOnDisk(old.slab_id, old.record_off);
      it->second = fresh;
    } else {
      st.map.emplace(std::move(ikey), fresh);
    }
  }
  return true;
}

bool SlabStore::Append(uint8_t kind, const std::string& key,
                       const char* data, size_t len, bool durable,
                       std::string* err) {
  return AppendInternal(kind, key, data, len, durable, nullptr, err);
}

bool SlabStore::Lookup(uint8_t kind, const std::string& key,
                       Slot* slot) const {
  std::string ikey = IndexKey(kind, key);
  const IndexStripe& st = index_[StripeFor(ikey)];
  std::lock_guard<RankedMutex> lk(st.mu);
  auto it = st.map.find(ikey);
  if (it == st.map.end()) return false;
  *slot = it->second;
  return true;
}

bool SlabStore::Has(uint8_t kind, const std::string& key) const {
  Slot s;
  return Lookup(kind, key, &s);
}

bool SlabStore::Read(uint8_t kind, const std::string& key,
                     std::string* out) const {
  // Lookup -> open -> pread, retried through a fresh lookup: a
  // compaction may unlink the slab between lookup and open, but it
  // re-appended (and re-indexed) the record before doing so, so a
  // fresh lookup lands on a live copy.  An fd opened before the unlink
  // keeps reading valid bytes (POSIX), so only the open can race — but
  // back-to-back compaction rounds can move the record again, so the
  // retry is a small loop, not a single second chance.
  for (int attempt = 0; attempt < 5; ++attempt) {
    Slot s;
    if (!Lookup(kind, key, &s)) return false;
    int fd = open(SlabPath(s.slab_id).c_str(), O_RDONLY);
    if (fd < 0) continue;
    out->resize(static_cast<size_t>(s.payload_len));
    bool ok = PreadFull(fd, out->data(), s.payload_len, s.payload_off);
    close(fd);
    if (ok) return true;
  }
  return false;
}

bool SlabStore::ReadSlice(uint8_t kind, const std::string& key,
                          int64_t offset, int64_t len, char* dst) const {
  for (int attempt = 0; attempt < 5; ++attempt) {
    Slot s;
    if (!Lookup(kind, key, &s)) return false;
    if (offset < 0 || len < 0 || offset + len > s.payload_len) return false;
    int fd = open(SlabPath(s.slab_id).c_str(), O_RDONLY);
    if (fd < 0) continue;
    bool ok = PreadFull(fd, dst, len, s.payload_off + offset);
    close(fd);
    if (ok) return true;
  }
  return false;
}

void SlabStore::ReadSlices(uint8_t kind, const SliceRead* reqs, size_t n,
                           bool* ok, int64_t* batches,
                           int64_t* vec_spans) const {
  // Records appended back-to-back sit header + key apart on disk, so
  // recipe-adjacent chunks coalesce once gaps up to a few records are
  // bridged; 4 KB keeps the wasted read under one page per seam.
  constexpr int64_t kMaxGap = 4096;
  constexpr size_t kMaxRunItems = 60;  // + bridge iovs stays far under IOV_MAX
  struct Item {
    int64_t start = 0;  // absolute file offset of the slice
    int64_t len = 0;
    char* dst = nullptr;
    size_t req = 0;
  };
  std::map<int64_t, std::vector<Item>> by_slab;
  for (size_t i = 0; i < n; ++i) {
    ok[i] = false;
    Slot s;
    if (!Lookup(kind, *reqs[i].key, &s)) continue;
    if (reqs[i].offset < 0 || reqs[i].len < 0 ||
        reqs[i].offset + reqs[i].len > s.payload_len)
      continue;
    by_slab[s.slab_id].push_back(Item{s.payload_off + reqs[i].offset,
                                      reqs[i].len, reqs[i].dst, i});
  }
  std::string scrap(static_cast<size_t>(kMaxGap), '\0');
  for (auto& [slab_id, items] : by_slab) {
    int fd = open(SlabPath(slab_id).c_str(), O_RDONLY);
    if (fd < 0) continue;  // compaction unlinked it; per-req retry path
    std::sort(items.begin(), items.end(),
              [](const Item& a, const Item& b) { return a.start < b.start; });
    size_t run_begin = 0;
    while (run_begin < items.size()) {
      // Grow the run while the next slice starts past the current end
      // (preadv only reads forward) within bridging distance.
      size_t run_end = run_begin + 1;
      int64_t end_off = items[run_begin].start + items[run_begin].len;
      while (run_end < items.size() &&
             run_end - run_begin < kMaxRunItems &&
             items[run_end].start >= end_off &&
             items[run_end].start - end_off <= kMaxGap) {
        end_off = items[run_end].start + items[run_end].len;
        ++run_end;
      }
      struct iovec iov[2 * kMaxRunItems + 1];
      int iovcnt = 0;
      int64_t cursor = items[run_begin].start;
      for (size_t i = run_begin; i < run_end; ++i) {
        if (items[i].start > cursor) {
          // Bridge the inter-record gap into the scrap buffer; every
          // gap may share it — the bytes are discarded.
          iov[iovcnt].iov_base = scrap.data();
          iov[iovcnt].iov_len = static_cast<size_t>(items[i].start - cursor);
          ++iovcnt;
        }
        iov[iovcnt].iov_base = items[i].dst;
        iov[iovcnt].iov_len = static_cast<size_t>(items[i].len);
        ++iovcnt;
        cursor = items[i].start + items[i].len;
      }
      if (PreadvAll(fd, iov, iovcnt, items[run_begin].start)) {
        *batches += 1;
        *vec_spans += static_cast<int64_t>(run_end - run_begin);
        for (size_t i = run_begin; i < run_end; ++i) ok[items[i].req] = true;
      }
      // A failed run leaves its requests ok = false: the caller's
      // per-request ReadSlice retry owns compaction races.
      run_begin = run_end;
    }
    close(fd);
  }
}

bool SlabStore::MarkDead(uint8_t kind, const std::string& key,
                         int64_t* payload_len_out) {
  std::lock_guard<RankedMutex> lk(mu_);
  std::string ikey = IndexKey(kind, key);
  IndexStripe& st = index_[StripeFor(ikey)];
  Slot s;
  {
    std::lock_guard<RankedMutex> ilk(st.mu);
    auto it = st.map.find(ikey);
    if (it == st.map.end()) return false;
    s = it->second;
    st.map.erase(it);
    AccountDeadLocked(s.slab_id, RecordExtent(key.size(), s.payload_len));
  }
  FlagDeadOnDisk(s.slab_id, s.record_off);
  if (payload_len_out != nullptr) *payload_len_out = s.payload_len;
  return true;
}

void SlabStore::ForEachLiveMeta(
    uint8_t kind, const std::function<void(const RecordMeta&)>& fn) const {
  for (const IndexStripe& st : index_) {
    std::vector<RecordMeta> batch;
    {
      std::lock_guard<RankedMutex> lk(st.mu);
      for (const auto& [ikey, slot] : st.map) {
        if (static_cast<uint8_t>(ikey[0]) != kind) continue;
        batch.push_back(
            RecordMeta{ikey.substr(1), slot.payload_len, slot.mtime});
      }
    }
    for (const RecordMeta& m : batch) fn(m);
  }
}

void SlabStore::ForEachLive(
    uint8_t kind, const std::function<void(const std::string& key,
                                           const std::string& payload)>& fn)
    const {
  // Group live slots by slab and read each slab with ONE open and
  // offset-ordered preads: the boot recipe rebuild calls this with
  // every live recipe on the node, and a per-record open/close would
  // turn startup into millions of redundant syscalls.
  struct Item {
    std::string key;
    Slot slot;
  };
  std::map<int64_t, std::vector<Item>> by_slab;
  for (const IndexStripe& st : index_) {
    std::lock_guard<RankedMutex> lk(st.mu);
    for (const auto& [ikey, slot] : st.map)
      if (static_cast<uint8_t>(ikey[0]) == kind)
        by_slab[slot.slab_id].push_back(Item{ikey.substr(1), slot});
  }
  std::string payload;
  for (auto& [slab_id, items] : by_slab) {
    std::sort(items.begin(), items.end(),
              [](const Item& a, const Item& b) {
                return a.slot.payload_off < b.slot.payload_off;
              });
    int fd = open(SlabPath(slab_id).c_str(), O_RDONLY);
    for (const Item& it : items) {
      bool ok = false;
      if (fd >= 0) {
        payload.resize(static_cast<size_t>(it.slot.payload_len));
        ok = PreadFull(fd, payload.data(), it.slot.payload_len,
                      it.slot.payload_off);
      }
      // Slab vanished/moved under us (a concurrent compaction):
      // per-key Read() re-resolves through a fresh lookup.
      if (!ok) ok = Read(kind, it.key, &payload);
      if (ok) fn(it.key, payload);
    }
    if (fd >= 0) close(fd);
  }
}

void SlabStore::ScanOneSlab(
    int64_t slab_id, const std::string& path,
    std::vector<std::pair<std::string, Slot>>* dups) {
  int fd = open(path.c_str(), O_RDONLY);
  if (fd < 0) return;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    close(fd);
    return;
  }
  int64_t size = st.st_size;
  SlabInfo info;
  info.size_bytes = size;
  int64_t off = 0;
  std::string hdr;
  while (off < size) {
    hdr.resize(kSlabRecordHeaderSize + kSlabKeyMaxLen);
    int64_t want = std::min<int64_t>(static_cast<int64_t>(hdr.size()),
                                     size - off);
    if (!PreadFull(fd, hdr.data(), want, off)) break;
    SlabRecordView v;
    if (!SlabDecodeRecord(hdr.data(), static_cast<size_t>(want), &v) ||
        off + v.record_len > size) {
      // Torn tail (crash mid-append): truncate it away so the file is a
      // clean record sequence again.  Anything after a corrupt header
      // is unreachable — same policy as the metrics journal's
      // torn-tail recovery.
      FDFS_LOG_WARN("slab %s: torn/corrupt record at offset %lld, "
                    "truncating %lld bytes",
                    path.c_str(), static_cast<long long>(off),
                    static_cast<long long>(size - off));
      if (truncate(path.c_str(), off) != 0)
        FDFS_LOG_WARN("slab %s: truncate failed: %s", path.c_str(),
                      strerror(errno));
      size = off;
      info.size_bytes = size;
      break;
    }
    int64_t extent = v.record_len;
    if (v.flags & kSlabFlagDead) {
      info.dead_slots++;
      info.dead_bytes += extent;
    } else {
      Slot slot;
      slot.slab_id = slab_id;
      slot.record_off = off;
      slot.payload_off = off + static_cast<int64_t>(kSlabRecordHeaderSize +
                                                    v.key.size());
      slot.payload_len = v.payload_len;
      slot.mtime = v.mtime;
      std::string ikey = IndexKey(v.kind, v.key);
      IndexStripe& stripe = index_[StripeFor(ikey)];
      {
        // Boot runs single-threaded, but tests rebuild a store that
        // already served — take the stripe lock like the chunk-store
        // rebuild does (mu_ is held: rank 92 -> 94).
        std::lock_guard<RankedMutex> ilk(stripe.mu);
        auto it = stripe.map.find(ikey);
        if (it != stripe.map.end()) {
          // Duplicate key: a crash between a replace/compaction append
          // and the old record's dead mark.  Scanning ascending (slab
          // id, offset) means the NEW record is the one in hand — the
          // indexed older one dies.
          dups->push_back({ikey, it->second});
          it->second = slot;
        } else {
          stripe.map.emplace(std::move(ikey), slot);
        }
      }
      info.live_slots++;
      info.live_bytes += extent;
    }
    off += extent;
  }
  close(fd);
  slabs_[slab_id] = info;
}

void SlabStore::ScanRebuild() {
  std::lock_guard<RankedMutex> lk(mu_);
  if (active_fd_ >= 0) {
    close(active_fd_);
    active_fd_ = -1;
  }
  if (flag_fd_ >= 0) {
    close(flag_fd_);
    flag_fd_ = -1;
  }
  slabs_.clear();
  for (IndexStripe& st : index_) {
    std::lock_guard<RankedMutex> ilk(st.mu);
    st.map.clear();
  }
  slots_live_ = slots_dead_ = 0;
  bytes_live_ = bytes_dead_ = 0;

  std::vector<int64_t> ids;
  DIR* d = opendir(dir_.c_str());
  if (d != nullptr) {
    struct dirent* de;
    while ((de = readdir(d)) != nullptr) {
      std::string name = de->d_name;
      if (name.size() != 15 ||
          name.compare(name.size() - 5, 5, ".slab") != 0)
        continue;
      char* end = nullptr;
      long long id = strtoll(name.c_str(), &end, 10);
      if (end == name.c_str() || id <= 0) continue;
      ids.push_back(id);
    }
    closedir(d);
  }
  std::sort(ids.begin(), ids.end());
  // The boot scan runs single-threaded before serving, so the index
  // stripes are touched without their locks only through ScanOneSlab's
  // direct map access — but tests rebuild a store that already served,
  // so hold each stripe lock around the whole scan?  The scan touches
  // every stripe per record; instead the maps were cleared above under
  // their locks and this thread is the only writer during a rebuild
  // (ChunkStore::RebuildFromRecipes documents the same contract).
  std::vector<std::pair<std::string, Slot>> dups;
  for (int64_t id : ids) ScanOneSlab(id, SlabPath(id), &dups);
  for (const auto& [ikey, old] : dups) {
    AccountDeadLocked(old.slab_id,
                      RecordExtent(ikey.size() - 1, old.payload_len));
    // AccountDeadLocked moved it live->dead but the old record was
    // counted live during its own slab's scan, so totals balance.
    FlagDeadOnDisk(old.slab_id, old.record_off);
  }
  int64_t live_slots = 0, dead_slots = 0, live_bytes = 0, dead_bytes = 0;
  for (const auto& [id, info] : slabs_) {
    live_slots += info.live_slots;
    dead_slots += info.dead_slots;
    live_bytes += info.live_bytes;
    dead_bytes += info.dead_bytes;
    if (id >= active_id_) active_id_ = id;
  }
  slots_live_ = live_slots;
  slots_dead_ = dead_slots;
  bytes_live_ = live_bytes;
  bytes_dead_ = dead_bytes;
  files_.store(static_cast<int64_t>(slabs_.size()),
               std::memory_order_relaxed);
  if (active_id_ > 0) {
    auto it = slabs_.find(active_id_);
    active_size_ = it != slabs_.end() ? it->second.size_bytes : 0;
  }
  if (!slabs_.empty())
    FDFS_LOG_INFO("slab store %s: %zu slabs, %lld live slots (%lld bytes), "
                  "%lld dead slots (%lld bytes)",
                  dir_.c_str(), slabs_.size(),
                  static_cast<long long>(live_slots),
                  static_cast<long long>(live_bytes),
                  static_cast<long long>(dead_slots),
                  static_cast<long long>(dead_bytes));
}

SlabStore::CompactResult SlabStore::Compact(
    const std::function<void(int64_t)>& pace,
    const std::function<bool()>& stop) {
  CompactResult res;
  // Victims that stayed alive this round (a corrupt record left in
  // place, an unreadable file): excluded so ONE stuck slab never
  // starves the rest of the round — they retry next pass, after the
  // quarantine machinery marks their bad slots dead.
  std::set<int64_t> skip;
  // A call compacts the slabs that were there when it began.  The slab
  // it rolls live records into, and every later one, wait for the next
  // call: a foreground that kills records as fast as a round copies them
  // would hand this loop a newly ripe active slab every round, and the
  // scrub pass behind it would end only when the foreground does.
  int64_t newest = 0;
  {
    std::lock_guard<RankedMutex> lk(mu_);
    for (const auto& [id, info] : slabs_) newest = std::max(newest, id);
  }
  for (;;) {
    if (stop != nullptr && stop()) return res;
    // Pick the deadest eligible victim past the dead-share threshold
    // (or fully dead).  The ACTIVE slab is eligible too — it is retired
    // first (fd closed, next append rolls to a fresh id) so a small
    // store whose only slab went mostly dead still reclaims.
    int64_t victim = 0, victim_dead = 0;
    bool victim_empty = false;
    {
      std::lock_guard<RankedMutex> lk(mu_);
      for (const auto& [id, info] : slabs_) {
        if (skip.count(id) || id > newest) continue;
        bool empty = info.live_slots == 0 && id != active_id_;
        bool ripe = empty ||
                    (info.size_bytes > 0 &&
                     info.dead_bytes * 100 >= info.size_bytes *
                                                  min_dead_pct_);
        if (!ripe) continue;
        if (victim == 0 || info.dead_bytes > victim_dead) {
          victim = id;
          victim_dead = info.dead_bytes;
          victim_empty = empty;
        }
      }
      if (victim != 0 && victim == active_id_) {
        if (active_fd_ >= 0) {
          close(active_fd_);
          active_fd_ = -1;
        }
        // Force EnsureActiveLocked to roll: appends (including this
        // compaction's own re-appends) land in a fresh slab.
        active_size_ = slab_bytes_;
      }
    }
    if (victim == 0) return res;

    std::string path = SlabPath(victim);
    if (!victim_empty) {
      // Copy phase: walk the victim's records; every record still
      // indexed at this exact location is live and gets re-appended
      // (verified first) before the old copy dies.
      int fd = open(path.c_str(), O_RDONLY);
      if (fd < 0) return res;
      struct stat st;
      int64_t size = fstat(fd, &st) == 0 ? st.st_size : 0;
      int64_t off = 0;
      std::string buf;
      bool scan_ok = true;
      while (off < size) {
        if (stop != nullptr && stop()) {
          close(fd);
          return res;  // victim left as-is; next pass resumes
        }
        buf.resize(kSlabRecordHeaderSize + kSlabKeyMaxLen);
        int64_t want = std::min<int64_t>(
            static_cast<int64_t>(buf.size()), size - off);
        SlabRecordView v;
        if (!PreadFull(fd, buf.data(), want, off) ||
            !SlabDecodeRecord(buf.data(), static_cast<size_t>(want), &v) ||
            off + v.record_len > size) {
          FDFS_LOG_WARN("slab compact %s: unreadable record at %lld, "
                        "aborting this slab",
                        path.c_str(), static_cast<long long>(off));
          scan_ok = false;
          break;
        }
        Slot here;
        bool live = Lookup(v.kind, v.key, &here) && here.slab_id == victim &&
                    here.record_off == off;
        if (live) {
          std::string payload;
          payload.resize(static_cast<size_t>(v.payload_len));
          if (!PreadFull(fd, payload.data(), v.payload_len,
                        here.payload_off)) {
            scan_ok = false;
            break;
          }
          if (pace != nullptr) pace(v.record_len);
          // Re-verify before the bytes move: a chunk IS its digest; a
          // recipe carries the payload CRC.  Failures stay in place and
          // go up to the quarantine/heal machinery — the slab is then
          // finished by a later pass once the bad slot is marked dead.
          bool good =
              v.kind == kSlabKindChunk
                  ? Sha1(payload.data(), payload.size()).Hex() == v.key
                  : Crc32(payload.data(), payload.size()) == v.payload_crc32;
          if (!good) {
            if (v.kind == kSlabKindChunk)
              res.corrupt_chunk_keys.push_back(v.key);
            else
              res.corrupt_recipe_keys.push_back(v.key);
          } else {
            std::string err;
            // Recipes keep their durability across the move: the copy
            // must be fsync'd before the only other copy's slab dies.
            // Chunks match the flat path (never fsync'd).
            if (!AppendInternal(v.kind, v.key, payload.data(),
                                payload.size(),
                                /*durable=*/v.kind == kSlabKindRecipe,
                                &here, &err)) {
              FDFS_LOG_WARN("slab compact: re-append of %s failed: %s",
                            v.key.c_str(), err.c_str());
              scan_ok = false;
              break;
            }
            res.copied_records++;
            compacted_bytes_.fetch_add(v.record_len,
                                       std::memory_order_relaxed);
          }
        } else if (pace != nullptr) {
          pace(kSlabRecordHeaderSize);  // header-only visit
        }
        off += v.record_len;
      }
      close(fd);
      if (!scan_ok) {
        skip.insert(victim);
        continue;
      }
    }

    // Unlink phase — only when the victim is now fully dead (corrupt
    // leftovers keep it alive until quarantine marks them dead; skip
    // it and keep compacting the rest of the round).
    bool alive = false;
    {
      std::lock_guard<RankedMutex> lk(mu_);
      auto it = slabs_.find(victim);
      if (it == slabs_.end()) {
        skip.insert(victim);
        continue;
      }
      alive = it->second.live_slots != 0;
      if (alive) {
        skip.insert(victim);
      } else {
        if (flag_fd_ >= 0 && flag_fd_slab_ == victim) {
          close(flag_fd_);
          flag_fd_ = -1;
        }
        slots_dead_.fetch_sub(it->second.dead_slots,
                              std::memory_order_relaxed);
        bytes_dead_.fetch_sub(it->second.dead_bytes,
                              std::memory_order_relaxed);
        res.reclaimed_bytes += it->second.size_bytes;
        slabs_.erase(it);
        files_.store(static_cast<int64_t>(slabs_.size()),
                     std::memory_order_relaxed);
      }
    }
    if (alive) continue;
    if (unlink(path.c_str()) != 0 && errno != ENOENT)
      FDFS_LOG_WARN("slab compact: unlink %s: %s", path.c_str(),
                    strerror(errno));
    compactions_.fetch_add(1, std::memory_order_relaxed);
    res.slabs_compacted++;
    FDFS_LOG_INFO("slab compact: slab %lld reclaimed (%lld records copied)",
                  static_cast<long long>(victim),
                  static_cast<long long>(res.copied_records));
  }
}

}  // namespace fdfs
