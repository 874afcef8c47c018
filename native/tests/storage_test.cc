// Unit tests for binlog + store + dedup units (the daemon itself is
// integration-tested from pytest via the Python client).
#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <string>
#include <thread>
#include <vector>

#include "common/bytes.h"
#include "common/trace.h"
#include "storage/binlog.h"
#include "storage/chunkstore.h"
#include "storage/ecstore.h"
#include "storage/dedup.h"
#include "storage/ingest.h"
#include "storage/store.h"
#include "storage/trunk.h"

static int g_failures = 0;

#define CHECK(cond)                                                        \
  do {                                                                     \
    if (!(cond)) {                                                         \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
      ++g_failures;                                                        \
    }                                                                      \
  } while (0)

using namespace fdfs;

static std::string TempDir() {
  char tmpl[] = "/tmp/fdfs_storage_test_XXXXXX";
  return mkdtemp(tmpl);
}

static void TestBinlogRecordCodec() {
  BinlogRecord rec;
  rec.timestamp = 1700000000;
  rec.op = 'C';
  rec.filename = "M00/AA/BB/name.jpg";
  std::string line = FormatBinlogRecord(rec);
  CHECK(line == "1700000000 C M00/AA/BB/name.jpg\n");
  auto back = ParseBinlogRecord(line);
  CHECK(back.has_value());
  CHECK(back->timestamp == rec.timestamp);
  CHECK(back->op == 'C');
  CHECK(back->filename == rec.filename);
  CHECK(back->extra.empty());

  rec.op = 'L';
  rec.extra = "M00/CC/DD/src.jpg";
  auto back2 = ParseBinlogRecord(FormatBinlogRecord(rec));
  CHECK(back2.has_value());
  CHECK(back2->extra == "M00/CC/DD/src.jpg");

  CHECK(!ParseBinlogRecord("garbage\n").has_value());
  CHECK(!ParseBinlogRecord("17 \n").has_value());
  CHECK(!ParseBinlogRecord("").has_value());
}

static void TestBinlogWriteReadResume() {
  std::string dir = TempDir();
  std::string err;
  BinlogWriter w;
  CHECK(w.Init(dir, 1 << 20, &err));
  for (int i = 0; i < 10; ++i)
    CHECK(w.Append('C', "M00/00/00/file" + std::to_string(i)));
  w.Flush();

  BinlogReader r;
  CHECK(r.Init(dir, dir + "/peer.mark", &err));
  for (int i = 0; i < 5; ++i) {
    auto rec = r.Next();
    CHECK(rec.has_value());
    CHECK(rec->filename == "M00/00/00/file" + std::to_string(i));
  }
  CHECK(r.SaveMark());

  // Fresh reader resumes from the mark.
  BinlogReader r2;
  CHECK(r2.Init(dir, dir + "/peer.mark", &err));
  auto rec = r2.Next();
  CHECK(rec.has_value());
  CHECK(rec->filename == "M00/00/00/file5");
  for (int i = 6; i < 10; ++i) CHECK(r2.Next().has_value());
  CHECK(!r2.Next().has_value());  // caught up

  // New writes become visible to the same reader (tailing).
  CHECK(w.Append('D', "M00/00/00/file3"));
  w.Flush();
  auto tail = r2.Next();
  CHECK(tail.has_value());
  CHECK(tail->op == 'D');
}

static void TestBinlogRotation() {
  std::string dir = TempDir();
  std::string err;
  BinlogWriter w;
  CHECK(w.Init(dir, 128, &err));  // tiny rotate size
  for (int i = 0; i < 20; ++i) CHECK(w.Append('C', "M00/00/00/f" + std::to_string(i)));
  w.Flush();
  CHECK(w.file_index() >= 1);  // rotated at least once

  BinlogReader r;
  CHECK(r.Init(dir, dir + "/m.mark", &err));
  int count = 0;
  while (r.Next().has_value()) ++count;
  CHECK(count == 20);  // reader follows rotation
}

static void TestCpuDedup() {
  std::string dir = TempDir();
  CpuDedup d(dir + "/dedup_index.dat");
  CHECK(!d.Judge("abc", 10).duplicate);
  d.Commit("abc", "group1/M00/00/00/x.bin");
  auto v = d.Judge("abc", 10);
  CHECK(v.duplicate);
  CHECK(v.dup_of == "group1/M00/00/00/x.bin");
  // snapshot round-trip
  CHECK(d.Save());
  CpuDedup d2(dir + "/dedup_index.dat");
  CHECK(d2.LoadSnapshot());
  CHECK(d2.Judge("abc", 10).duplicate);
  // forget
  d2.Forget("group1/M00/00/00/x.bin");
  CHECK(!d2.Judge("abc", 10).duplicate);
}

// dedup.cc has no Conn: it reaches the request's recorder through the
// thread-local the dio handler binds, and does nothing without one.
static void TestDedupRecordsIntoBoundStageTrace() {
  std::string dir = TempDir();
  CpuDedup d(dir + "/dedup_index.dat");
  std::string data(256 * 1024, '\0');
  for (size_t i = 0; i < data.size(); ++i)
    data[i] = static_cast<char>((i * 2654435761u) >> 13);
  std::vector<ChunkFp> fps;
  CHECK(d.FingerprintChunks(1, data.data(), data.size(), 0, &fps));  // unbound
  StageTrace t;
  t.Reset();
  {
    StageTraceBinding bind(&t);
    StageScope fp(&t, Stage::kFingerprint);
    fps.clear();
    CHECK(d.FingerprintChunks(1, data.data(), data.size(), 0, &fps));
  }
  CHECK(!fps.empty());
  CHECK(t.n == 2);
  CHECK(t.iv[1].stage == Stage::kCdc && t.iv[1].parent == 0);
  CHECK(t.iv[0].start_us <= t.iv[1].start_us &&
        t.iv[1].end_us <= t.iv[0].end_us);
  CHECK(t.Sum(Stage::kCdc) == t.iv[1].end_us - t.iv[1].start_us);
  CHECK(t.Sum(Stage::kFpLock) == 0);  // the cpu plugin has no connection
}

static void TestStoreInit() {
  std::string dir = TempDir();
  StorageConfig cfg;
  cfg.base_path = dir;
  cfg.store_paths = {dir};
  cfg.subdir_count_per_path = 4;
  StoreManager sm;
  std::string err;
  CHECK(sm.Init(cfg, &err));
  struct stat st;
  CHECK(stat((dir + "/data/03/03").c_str(), &st) == 0);
  CHECK(stat((dir + "/data/.data_init_flag").c_str(), &st) == 0);
  // second init is a no-op (flag present)
  CHECK(sm.Init(cfg, &err));
  // uniquifier wraps at 12 bits
  for (int i = 0; i < 5000; ++i) {
    int u = sm.NextUniquifier();
    CHECK(u >= 0 && u <= 0xFFF);
  }
  std::string t1 = sm.NewTmpPath(0), t2 = sm.NewTmpPath(0);
  CHECK(t1 != t2);
}


static void TestTrunkAllocator() {
  std::string dir = TempDir();
  TrunkAllocator alloc;
  std::string err;
  CHECK(alloc.Init(dir, 1 << 20, &err));  // 1 MB trunk files for the test
  CHECK(alloc.trunk_file_count() == 0);

  // First alloc creates a trunk file and splits it.
  auto a = alloc.Alloc(1000);
  CHECK(a.has_value());
  CHECK(a->trunk_id == 0 && a->offset == 0);
  CHECK(a->alloc_size >= 1000 + kTrunkHeaderSize);
  CHECK(alloc.trunk_file_count() == 1);

  // Second alloc lands after the first (split remainder).
  auto b = alloc.Alloc(5000);
  CHECK(b.has_value());
  CHECK(b->trunk_id == 0);
  CHECK(b->offset == a->alloc_size);

  // Write payloads and read them back.
  std::string pa(1000, 'x'), pb(5000, 'y');
  CHECK(WriteSlotPayload(dir, *a, pa, 111, &err));
  CHECK(WriteSlotPayload(dir, *b, pb, 222, &err));
  auto ra = ReadSlotPayload(dir, *a, 1000);
  CHECK(ra.has_value() && *ra == pa);

  // Free A; the same-size alloc reuses its exact slot.
  CHECK(alloc.Free(*a));
  auto c = alloc.Alloc(1000);
  CHECK(c.has_value());
  CHECK(c->trunk_id == a->trunk_id && c->offset == a->offset);

  // Freed slot no longer readable as data.
  CHECK(alloc.Free(*c));
  CHECK(!ReadSlotPayload(dir, *a, 1000).has_value());

  // Pool vs on-disk headers agree.
  std::string report;
  CHECK(alloc.VerifyFreeMap(&report) == 0);

  // Scan-rebuild (failover path): a fresh allocator sees the same world
  // and will not double-allocate B's live slot.
  TrunkAllocator alloc2;
  CHECK(alloc2.Init(dir, 1 << 20, &err));
  CHECK(alloc2.trunk_file_count() == 1);
  CHECK(alloc2.VerifyFreeMap(&report) == 0);
  auto d = alloc2.Alloc(5000);
  CHECK(d.has_value());
  CHECK(!(d->trunk_id == b->trunk_id && d->offset == b->offset));
  auto rb = ReadSlotPayload(dir, *b, 5000);
  CHECK(rb.has_value() && *rb == pb);

  // Oversized request refused; trunk-file exhaustion rolls to a new file.
  CHECK(!alloc2.Alloc(2 << 20).has_value());
}

static void TestTrunkReplicaWrite() {
  // WriteSlotPayload must create + extend the file on a replica that has
  // never allocated anything (sync replay path).
  std::string dir = TempDir();
  TrunkLocation loc;
  loc.trunk_id = 7;
  loc.offset = 123 * kTrunkAlignment;
  loc.alloc_size = 4 * kTrunkAlignment;
  std::string payload(900, 'z'), err;
  CHECK(WriteSlotPayload(dir, loc, payload, 42, &err));
  auto back = ReadSlotPayload(dir, loc, 900);
  CHECK(back.has_value() && *back == payload);
  CHECK(MarkSlotFree(dir, loc));
  CHECK(!ReadSlotPayload(dir, loc, 900).has_value());
}

static void TestTrunkReserveAndCompaction() {
  std::string dir = TempDir();
  TrunkAllocator alloc;
  std::string err;
  CHECK(alloc.Init(dir, 1 << 20, &err));
  CHECK(alloc.trunk_file_count() == 0);

  // Pre-allocation: demand a 3 MB reserve -> 3 fresh 1 MB trunk files,
  // all free; idempotent once satisfied.
  CHECK(alloc.EnsureFreeReserve(3 << 20) == 3);
  CHECK(alloc.trunk_file_count() == 3);
  CHECK(alloc.free_bytes() == 3 << 20);
  CHECK(alloc.EnsureFreeReserve(3 << 20) == 0);

  // Allocations now come from the reserve without creating files.
  auto a = alloc.Alloc(4000);
  CHECK(a.has_value());
  CHECK(alloc.trunk_file_count() == 3);

  // Compaction: with one slot live, exactly the OTHER fully-free files
  // beyond the keep=1 reserve are reclaimed.
  CHECK(alloc.ReclaimEmptyFiles(/*keep=*/1) == 1);
  std::string report;
  CHECK(alloc.VerifyFreeMap(&report) == 0);

  // The live slot still reads back; freeing it makes its file
  // reclaimable too (keep=0 clears everything).
  std::string pa(4000, 'q');
  CHECK(WriteSlotPayload(dir, *a, pa, 7, &err));
  auto ra = ReadSlotPayload(dir, *a, 4000);
  CHECK(ra.has_value() && *ra == pa);
  CHECK(alloc.Free(*a));
  CHECK(alloc.ReclaimEmptyFiles(/*keep=*/0) >= 1);

  // A scan-rebuild of the compacted dir agrees with the pool.
  TrunkAllocator alloc2;
  CHECK(alloc2.Init(dir, 1 << 20, &err));
  CHECK(alloc2.VerifyFreeMap(&report) == 0);
}

// -- chunk-store integrity engine (scrub/GC/quarantine) --------------------

static std::string Sha1HexOf(const std::string& data) {
  return Sha1(data.data(), data.size()).Hex();
}

static std::string ChunkStoreDir() {
  // ChunkStore expects the store path's data/ dir to exist (the daemon's
  // StoreManager pre-creates it).
  std::string dir = TempDir();
  mkdir((dir + "/data").c_str(), 0755);
  return dir;
}

static bool FileExists(const std::string& p) {
  struct stat st;
  return stat(p.c_str(), &st) == 0;
}

static void FlipFirstByte(const std::string& p) {
  FILE* f = fopen(p.c_str(), "r+b");
  CHECK(f != nullptr);
  int c = fgetc(f);
  fseek(f, 0, SEEK_SET);
  fputc(c ^ 0xFF, f);
  fclose(f);
}

static void TestChunkStoreGcGraceAndPins() {
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, /*gc_grace_s=*/60);
  std::string payload(4096, 'x');
  std::string dig = Sha1HexOf(payload);
  bool existed = false;
  std::string err;
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
  CHECK(!existed);

  Recipe r;
  r.logical_size = 4096;
  r.chunks.push_back({dig, 4096});

  // Grace mode: the last unref parks the chunk instead of unlinking.
  cs.UnrefAll(r);
  CHECK(FileExists(cs.ChunkPath(dig)));
  CHECK(cs.gc_pending_chunks() == 1);
  CHECK(cs.gc_pending_bytes() == 4096);

  // Inside the grace window nothing is reclaimed.
  int64_t bytes = 0;
  CHECK(cs.GcSweep(time(nullptr), &bytes) == 0);
  CHECK(bytes == 0);

  // REGRESSION (ISSUE 4 satellite): a phase-1 upload session pins the
  // chunk via PinAndMask — the pin probe runs under the SAME lock as
  // the sweep's unlink, and a pinned zero-ref chunk must survive a
  // sweep even past its grace.
  std::string need = cs.PinAndMask(r);
  CHECK(need.size() == 1);
  CHECK(need[0] == 1);  // zero-ref reads as "needed" (client re-ships)
  bytes = 0;
  CHECK(cs.GcSweep(time(nullptr) + 3600, &bytes) == 0);
  CHECK(FileExists(cs.ChunkPath(dig)));

  // The session commits: PutAndRef resurrects the parked bytes without
  // rewriting them.
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
  CHECK(existed);
  CHECK(cs.gc_pending_chunks() == 0);
  cs.UnpinRecipe(r);
  bytes = 0;
  CHECK(cs.GcSweep(time(nullptr) + 3600, &bytes) == 0);  // live again

  // Drop the ref for real: past the grace (and unpinned) the sweep
  // reclaims bytes and count.
  cs.UnrefAll(r);
  bytes = 0;
  CHECK(cs.GcSweep(time(nullptr) + 3600, &bytes) == 1);
  CHECK(bytes == 4096);
  CHECK(!FileExists(cs.ChunkPath(dig)));
  CHECK(cs.gc_pending_chunks() == 0);
}

static void TestChunkStoreEagerModeUnchanged() {
  // gc_grace_s == 0 keeps the original semantics: unlink on last unref.
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, 0);
  std::string payload(1024, 'y');
  std::string dig = Sha1HexOf(payload);
  bool existed = false;
  std::string err;
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
  Recipe r;
  r.chunks.push_back({dig, 1024});
  cs.UnrefAll(r);
  CHECK(!FileExists(cs.ChunkPath(dig)));

  // Pinned delete still defers to the last unpin (stream semantics).
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
  cs.PinRecipe(r);
  cs.UnrefAll(r);
  CHECK(FileExists(cs.ChunkPath(dig)));
  cs.UnpinRecipe(r);
  CHECK(!FileExists(cs.ChunkPath(dig)));
}

static void TestChunkStoreQuarantineRepairHeal() {
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, 0);
  std::string payload(2048, 'q');
  std::string dig = Sha1HexOf(payload);
  bool existed = false;
  std::string err;
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));

  // Pinned chunks are exempt from quarantine (repair-in-place under a
  // live reader is unsafe).
  Recipe r;
  r.chunks.push_back({dig, 2048});
  cs.PinRecipe(r);
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kPinned);
  cs.UnpinRecipe(r);

  // A clean chunk survives a false accusation: the under-lock re-hash
  // overrules the caller (the lock-free verify read may have raced).
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kClean);
  FlipFirstByte(cs.ChunkPath(dig));
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kQuarantined);
  CHECK(!FileExists(cs.ChunkPath(dig)));
  CHECK(FileExists(cs.QuarantinePath(dig)));
  CHECK(cs.quarantined_chunks() == 1);
  std::string back;
  CHECK(!cs.ReadChunk(dig, 2048, &back));  // never served again
  // Quarantined chunks read as missing so peers/clients re-ship bytes.
  CHECK(cs.HaveMask({dig})[0] == 1);
  // The live snapshot skips it; the quarantined snapshot names it.
  CHECK(cs.SnapshotLive().empty());
  CHECK(cs.SnapshotQuarantined().size() == 1);
  CHECK(cs.SnapshotQuarantined()[0].length == 2048);

  // Replica repair restores the bytes and clears the quarantine mark.
  CHECK(cs.RepairChunk(dig, payload.data(), payload.size(), &err));
  CHECK(cs.quarantined_chunks() == 0);
  CHECK(!FileExists(cs.QuarantinePath(dig)));
  CHECK(cs.ReadChunk(dig, 2048, &back));
  CHECK(back == payload);

  // Heal-on-upload: quarantine again, then a PutAndRef carrying the
  // payload (dedup hit) restores the bytes as a side effect.
  FlipFirstByte(cs.ChunkPath(dig));
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kQuarantined);
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
  CHECK(existed);
  CHECK(cs.quarantined_chunks() == 0);
  CHECK(cs.ReadChunk(dig, 2048, &back));
  CHECK(back == payload);

  // A deleted chunk cannot be quarantined or repaired (kGone / false).
  Recipe both;
  both.chunks.push_back({dig, 2048});
  both.chunks.push_back({dig, 2048});  // two refs taken above
  cs.UnrefAll(both);
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kGone);
  CHECK(!cs.RepairChunk(dig, payload.data(), payload.size(), &err));
}

static void TestChunkStoreRebuildParksOrphansAndKeepsQuarantine() {
  std::string dir = ChunkStoreDir();
  std::string payload(4096, 'r');
  std::string dig = Sha1HexOf(payload);
  {
    ChunkStore cs(dir, 3600);
    bool existed = false;
    std::string err;
    CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));
    Recipe r;
    r.logical_size = 4096;
    r.chunks.push_back({dig, 4096});
    CHECK(WriteRecipeFile(dir + "/data/f.rcp", r, &err));
    // A second chunk never named by any recipe (an upload whose recipe
    // write crashed, or a zero-ref chunk awaiting GC at shutdown).
    std::string orphan(512, 'o');
    std::string odig = Sha1HexOf(orphan);
    CHECK(cs.PutAndRef(odig, orphan.data(), orphan.size(), &existed, &err));
    // Quarantine the recipe's (corrupted) chunk, then "restart".
    FlipFirstByte(cs.ChunkPath(dig));
    CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kQuarantined);
  }
  ChunkStore cs2(dir, 3600);
  cs2.RebuildFromRecipes();
  // The referenced chunk is still quarantined after restart (its bytes
  // must not be re-admitted), and the orphan is parked for GC instead
  // of dropped — the grace window is crash-safe.
  CHECK(cs2.quarantined_chunks() == 1);
  CHECK(cs2.unique_chunks() == 1);
  CHECK(cs2.gc_pending_chunks() == 1);
  CHECK(cs2.HaveMask({dig})[0] == 1);
  std::string err;
  CHECK(cs2.RepairChunk(dig, payload.data(), payload.size(), &err));
  std::string back;
  CHECK(cs2.ReadChunk(dig, 4096, &back));
  CHECK(back == payload);
  int64_t bytes = 0;
  CHECK(cs2.GcSweep(time(nullptr) + 7200, &bytes) == 1);
  CHECK(bytes == 512);
}

static void TestChunkStoreReadRecipeAndPinRange() {
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, 0);
  Recipe r;
  r.logical_size = 0;
  std::vector<std::string> digs;
  bool existed = false;
  std::string err;
  for (int i = 0; i < 3; ++i) {
    std::string pay(100, static_cast<char>('a' + i));
    digs.push_back(Sha1HexOf(pay));
    CHECK(cs.PutAndRef(digs.back(), pay.data(), pay.size(), &existed, &err));
    r.chunks.push_back({digs.back(), 100});
    r.logical_size += 100;
  }
  std::string rcp = dir + "/data/rng.rcp";
  CHECK(WriteRecipeFile(rcp, r, &err));

  // Mid-file range trims to the overlapping slice only.
  int64_t skip = -1;
  auto t = cs.ReadRecipeAndPinRange(rcp, 150, 100, &skip);
  CHECK(t.has_value() && t->logical_size == 300);
  CHECK(t->chunks.size() == 2 && skip == 50);
  CHECK(t->chunks[0].digest_hex == digs[1]);
  cs.UnpinRecipe(*t);

  // count 0 = to EOF; offset 0 covers everything.
  t = cs.ReadRecipeAndPinRange(rcp, 0, 0, &skip);
  CHECK(t.has_value() && t->chunks.size() == 3 && skip == 0);
  cs.UnpinRecipe(*t);

  // Offset past EOF: EMPTY slice (caller answers EINVAL), not nullopt.
  t = cs.ReadRecipeAndPinRange(rcp, 1000, 10, &skip);
  CHECK(t.has_value() && t->chunks.empty());
  cs.UnpinRecipe(*t);

  // A deleted chunk inside the range fails the pin (rollback, ENOENT);
  // a range NOT touching it still pins fine.
  Recipe one;
  one.chunks.push_back({digs[2], 100});
  cs.UnrefAll(one);
  CHECK(!cs.ReadRecipeAndPinRange(rcp, 150, 0, &skip).has_value());
  t = cs.ReadRecipeAndPinRange(rcp, 0, 150, &skip);
  CHECK(t.has_value() && t->chunks.size() == 2);
  cs.UnpinRecipe(*t);
}

static void TestChunkStoreReadCacheCoherence() {
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, 0, /*read_cache_bytes=*/1 << 20);
  std::string payload(4096, 'c');
  std::string dig = Sha1HexOf(payload);
  bool existed = false;
  std::string err;
  CHECK(cs.PutAndRef(dig, payload.data(), payload.size(), &existed, &err));

  bool hit = false;
  auto p = cs.ReadChunkCached(dig, 4096, &hit);
  CHECK(p != nullptr && !hit && *p == payload);
  p = cs.ReadChunkCached(dig, 4096, &hit);
  CHECK(p != nullptr && hit && *p == payload);
  CHECK(cs.cache_hits() == 1 && cs.cache_misses() == 1);
  CHECK(cs.cache_chunks() == 1 && cs.cache_bytes() == 4096);
  CHECK(cs.CacheLookup(dig, 4096) != nullptr);

  // Quarantine invalidates in the SAME lock acquisition: a jailed
  // chunk must never be served from the cache.
  FlipFirstByte(cs.ChunkPath(dig));
  CHECK(cs.Quarantine(dig) == ChunkStore::QuarantineResult::kQuarantined);
  CHECK(cs.CacheLookup(dig, 4096) == nullptr);
  p = cs.ReadChunkCached(dig, 4096, &hit);
  CHECK(p == nullptr && !hit);  // bytes are in quarantine/, unreadable
  CHECK(cs.cache_invalidations() == 1);

  // Repair restores service with the verified bytes (fresh read).
  CHECK(cs.RepairChunk(dig, payload.data(), payload.size(), &err));
  p = cs.ReadChunkCached(dig, 4096, &hit);
  CHECK(p != nullptr && !hit && *p == payload);

  // A held shared_ptr survives eviction/invalidation (a response mid-
  // scatter keeps its bytes), but the cache itself forgets the entry
  // when the delete's unlink retires the chunk.
  auto held = cs.ReadChunkCached(dig, 4096, &hit);
  Recipe r;
  r.chunks.push_back({dig, 4096});
  cs.UnrefAll(r);  // eager mode: unlink now
  CHECK(cs.CacheLookup(dig, 4096) == nullptr);
  CHECK(cs.ReadChunkCached(dig, 4096, &hit) == nullptr);
  CHECK(held != nullptr && *held == payload);

  // An insert racing a delete must not publish a stale entry: the
  // insert re-checks liveness under the stripe lock, so a dead digest
  // never enters the cache.
  CHECK(cs.cache_chunks() == 0);

  // Capacity bound: filling past cap evicts LRU-first and the byte
  // gauge stays under cap.
  ChunkStore small(ChunkStoreDir(), 0, /*read_cache_bytes=*/8 << 10);
  std::string first_dig;
  for (int i = 0; i < 4; ++i) {
    std::string pay(4 << 10, static_cast<char>('a' + i));
    std::string d = Sha1HexOf(pay);
    if (i == 0) first_dig = d;
    CHECK(small.PutAndRef(d, pay.data(), pay.size(), &existed, &err));
    CHECK(small.ReadChunkCached(d, 4 << 10, &hit) != nullptr);
  }
  CHECK(small.cache_bytes() <= (8 << 10));
  CHECK(small.cache_evictions() >= 2);
  CHECK(small.CacheLookup(first_dig, 4 << 10) == nullptr);  // LRU victim
}

// -- slab packing (ISSUE 9) -----------------------------------------------

static void TestSlabRecordCodec() {
  std::string payload = "slab payload bytes 0123456789";
  std::string key = Sha1(payload.data(), payload.size()).Hex();
  std::string rec =
      SlabEncodeRecord(kSlabKindChunk, key, payload.data(), payload.size(),
                       1700000000);
  CHECK(rec.size() == kSlabRecordHeaderSize + key.size() + payload.size());
  SlabRecordView v;
  CHECK(SlabDecodeRecord(rec.data(), rec.size(), &v));
  CHECK(v.kind == kSlabKindChunk);
  CHECK(v.key == key);
  CHECK(v.payload_len == static_cast<int64_t>(payload.size()));
  CHECK(v.alloc_len == v.payload_len);
  CHECK(v.mtime == 1700000000);
  CHECK(v.flags == 0);
  CHECK(v.payload_crc32 == Crc32(payload.data(), payload.size()));
  CHECK(v.record_len == static_cast<int64_t>(rec.size()));
  // The dead-flag flip must NOT invalidate the header CRC (it is
  // computed with flags zeroed) — MarkDead relies on this.
  std::string dead = rec;
  dead[6] = 0x01;
  SlabRecordView vd;
  CHECK(SlabDecodeRecord(dead.data(), dead.size(), &vd));
  CHECK(vd.flags == 0x01);
  // Any OTHER header corruption must fail the frame.
  std::string bad = rec;
  bad[10] ^= 0x40;
  CHECK(!SlabDecodeRecord(bad.data(), bad.size(), &v));
  bad = rec;
  bad[0] = 'X';
  CHECK(!SlabDecodeRecord(bad.data(), bad.size(), &v));
  CHECK(!SlabDecodeRecord(rec.data(), kSlabRecordHeaderSize - 1, &v));
}

static void TestSlabStoreAppendRescanCompact() {
  std::string dir = TempDir();
  std::string slabs = dir + "/slabs";
  auto payload_for = [](int i) {
    return std::string(200 + i, static_cast<char>('a' + (i % 26)));
  };
  auto key_for = [&](int i) {
    std::string p = payload_for(i);
    return Sha1(p.data(), p.size()).Hex();
  };
  {
    SlabStore ss(slabs, 1 << 20, 25);
    ss.ScanRebuild();  // empty dir: no-op
    std::string err;
    for (int i = 0; i < 20; ++i) {
      std::string p = payload_for(i);
      CHECK(ss.Append(kSlabKindChunk, key_for(i), p.data(), p.size(),
                      false, &err));
    }
    std::string rcp = "data/00/00/file.bin.rcp";
    CHECK(ss.Append(kSlabKindRecipe, rcp, "RECIPE", 6, true, &err));
    CHECK(ss.slots_live() == 21);
    CHECK(ss.slots_dead() == 0);
    CHECK(ss.files() == 1);
    std::string back;
    CHECK(ss.Read(kSlabKindChunk, key_for(3), &back));
    CHECK(back == payload_for(3));
    char slice[8];
    CHECK(ss.ReadSlice(kSlabKindChunk, key_for(3), 2, 8, slice));
    CHECK(memcmp(slice, payload_for(3).data() + 2, 8) == 0);
    CHECK(!ss.ReadSlice(kSlabKindChunk, key_for(3), 200, 100, slice));
    // Replace semantics: re-append of an existing key kills the old.
    std::string p5 = payload_for(5);
    CHECK(ss.Append(kSlabKindChunk, key_for(5), p5.data(), p5.size(),
                    false, &err));
    CHECK(ss.slots_live() == 21);
    CHECK(ss.slots_dead() == 1);
  }
  {
    // Boot rescan rebuilds the same index from raw headers.
    SlabStore ss(slabs, 1 << 20, 25);
    ss.ScanRebuild();
    CHECK(ss.slots_live() == 21);
    CHECK(ss.slots_dead() == 1);
    std::string back;
    CHECK(ss.Read(kSlabKindRecipe, "data/00/00/file.bin.rcp", &back));
    CHECK(back == "RECIPE");
    // Torn tail: append garbage, rescan truncates it away.
    std::string path;
    {
      char name[64];
      snprintf(name, sizeof(name), "%s/%010d.slab", slabs.c_str(), 1);
      path = name;
    }
    FILE* f = fopen(path.c_str(), "ab");
    CHECK(f != nullptr);
    fwrite("FSLBgarbage-torn-tail", 1, 21, f);
    fclose(f);
    struct stat st0;
    CHECK(stat(path.c_str(), &st0) == 0);
    SlabStore ss2(slabs, 1 << 20, 25);
    ss2.ScanRebuild();
    CHECK(ss2.slots_live() == 21);
    struct stat st1;
    CHECK(stat(path.c_str(), &st1) == 0);
    CHECK(st1.st_size == st0.st_size - 21);
    // Kill most slots, compact, and verify the survivors re-read
    // byte-identically from the new slab while the victim is gone.
    for (int i = 0; i < 16; ++i)
      CHECK(ss2.MarkDead(kSlabKindChunk, key_for(i)));
    int64_t before_files = ss2.files();
    auto res = ss2.Compact(nullptr, nullptr);
    (void)before_files;
    CHECK(res.slabs_compacted == 1);
    CHECK(res.reclaimed_bytes > 0);
    CHECK(ss2.slots_dead() == 0);
    CHECK(ss2.compactions() == 1);
    for (int i = 16; i < 20; ++i) {
      CHECK(ss2.Read(kSlabKindChunk, key_for(i), &back));
      CHECK(back == payload_for(i));
    }
    CHECK(ss2.Read(kSlabKindRecipe, "data/00/00/file.bin.rcp", &back));
    CHECK(back == "RECIPE");
    CHECK(stat(path.c_str(), &st1) != 0);  // victim unlinked
    // A foreground that kills records as fast as a round copies them
    // (here: from inside the pace callback) does not keep one call
    // going: the slab the live records rolled into waits for the next.
    CHECK(ss2.MarkDead(kSlabKindChunk, key_for(16)));
    CHECK(ss2.MarkDead(kSlabKindChunk, key_for(17)));
    int churned = 0;
    auto churn = [&](int64_t) {
      if (churned >= 64) return;  // the old loop would run on: bound the test
      std::string p(4096, static_cast<char>('A' + churned % 26));
      p += std::to_string(churned++);
      std::string key = Sha1(p.data(), p.size()).Hex(), err;
      CHECK(ss2.Append(kSlabKindChunk, key, p.data(), p.size(), false, &err));
      CHECK(ss2.MarkDead(kSlabKindChunk, key));
    };
    res = ss2.Compact(churn, nullptr);
    CHECK(res.slabs_compacted == 1);
    CHECK(churned > 0 && churned < 64);
    CHECK(ss2.slots_dead() == churned);
    res = ss2.Compact(nullptr, nullptr);  // the next call takes that slab
    CHECK(res.slabs_compacted == 1);
    CHECK(ss2.slots_dead() == 0);
    for (int i = 18; i < 20; ++i) {
      CHECK(ss2.Read(kSlabKindChunk, key_for(i), &back));
      CHECK(back == payload_for(i));
    }
  }
}

static void TestChunkStoreSlabEndToEnd() {
  std::string dir = TempDir();
  SlabOptions so;
  so.chunk_threshold = 4096;
  so.recipe_threshold = 4096;
  so.slab_bytes = 1 << 20;
  so.compact_min_dead_pct = 10;
  ChunkStore cs(dir, /*gc_grace_s=*/0, /*cache=*/1 << 20, so);
  cs.RebuildFromRecipes();
  std::string err;
  // Small chunks land in the slab (no per-chunk inode); big ones flat.
  std::string small(1000, 's'), big(8000, 'b');
  std::string dsmall = Sha1(small.data(), small.size()).Hex();
  std::string dbig = Sha1(big.data(), big.size()).Hex();
  bool existed = false;
  CHECK(cs.PutAndRef(dsmall, small.data(), small.size(), &existed, &err));
  CHECK(cs.PutAndRef(dbig, big.data(), big.size(), &existed, &err));
  struct stat st;
  CHECK(stat(cs.ChunkPath(dsmall).c_str(), &st) != 0);  // slab-resident
  CHECK(stat(cs.ChunkPath(dbig).c_str(), &st) == 0);    // flat
  CHECK(cs.slab_slots_live() == 1);
  std::string back;
  CHECK(cs.ReadChunk(dsmall, 1000, &back) && back == small);
  char part[16];
  CHECK(cs.ReadChunkSlice(dsmall, 10, 16, part));
  CHECK(memcmp(part, small.data() + 10, 16) == 0);
  bool hit = false;
  auto p = cs.ReadChunkCached(dsmall, 1000, &hit);
  CHECK(p != nullptr && *p == small && !hit);
  p = cs.ReadChunkCached(dsmall, 1000, &hit);
  CHECK(p != nullptr && hit);
  // Recipes below the threshold pack too: no sidecar inode.
  Recipe r;
  r.logical_size = 9000;
  r.chunks.push_back({dsmall, 1000});
  r.chunks.push_back({dbig, 8000});
  std::string rcp = dir + "/data/00/00/f.bin.rcp";
  StoreManager::EnsureParentDirs(rcp);
  CHECK(cs.StoreRecipe(rcp, r, &err));
  CHECK(stat(rcp.c_str(), &st) != 0);  // slab record, not an inode
  CHECK(cs.HasRecipe(rcp));
  auto got = cs.LoadRecipe(rcp);
  CHECK(got.has_value() && got->chunks.size() == 2 &&
        got->chunks[0].digest_hex == dsmall);
  auto pinned = cs.ReadRecipeAndPin(rcp);
  CHECK(pinned.has_value());
  cs.UnpinRecipe(*pinned);
  // RefOne names the length the store holds, in either layout (a
  // negotiated commit holds its recipe's length against it), and gives
  // none for a digest the store does not have.
  int64_t stored = 0;
  CHECK(cs.RefOne(dsmall, &stored) && stored == 1000);
  CHECK(cs.RefOne(dbig, &stored) && stored == 8000);
  CHECK(!cs.RefOne(std::string(40, '0'), &stored) && stored == 8000);
  cs.UnrefAll(r);  // the two references back: the recipe holds the rest
  // Boot rescan: refs rebuilt from the slab-resident recipe.
  ChunkStore cs2(dir, 0, 0, so);
  cs2.RebuildFromRecipes();
  CHECK(cs2.Has(dsmall) && cs2.Has(dbig));
  CHECK(cs2.RefOne(dsmall, &stored) && stored == 1000);
  CHECK(cs2.RefOne(dbig, &stored) && stored == 8000);
  cs2.UnrefAll(r);
  CHECK(cs2.ReadChunk(dsmall, 1000, &back) && back == small);
  // Quarantine a slab-resident chunk: record dies, bytes preserved in
  // quarantine/, heal-on-upload re-appends a fresh record.
  {
    SlabStore::Slot slot;
    CHECK(cs2.slab()->Lookup(kSlabKindChunk, dsmall, &slot));
    char name[64];
    snprintf(name, sizeof(name), "%s/data/slabs/%010lld.slab", dir.c_str(),
             static_cast<long long>(slot.slab_id));
    FILE* f = fopen(name, "r+b");
    CHECK(f != nullptr);
    fseek(f, static_cast<long>(slot.payload_off), SEEK_SET);
    fputc('X', f);
    fclose(f);
  }
  CHECK(cs2.Quarantine(dsmall) == ChunkStore::QuarantineResult::kQuarantined);
  CHECK(!cs2.ReadChunk(dsmall, 1000, &back));
  CHECK(cs2.IsQuarantined(dsmall));
  bool existed2 = false;
  CHECK(cs2.PutAndRef(dsmall, small.data(), small.size(), &existed2, &err));
  CHECK(existed2);
  CHECK(!cs2.IsQuarantined(dsmall));
  CHECK(cs2.ReadChunk(dsmall, 1000, &back) && back == small);
  // Delete -> dead accounting -> compaction reclaims, survivors intact.
  int64_t dead_before = cs2.slab_bytes_dead();
  int64_t rcp_bytes = 0;
  CHECK(cs2.RemoveRecipe(rcp, &rcp_bytes));
  CHECK(rcp_bytes > 0);
  Recipe unref;
  unref.chunks.push_back({dsmall, 1000});
  unref.chunks.push_back({dbig, 8000});
  cs2.UnrefAll(unref);
  CHECK(cs2.slab_bytes_dead() > dead_before);
  std::vector<ChunkStore::ChunkInfo> corrupt;
  int64_t reclaimed = 0;
  (void)cs2.CompactSlabs(nullptr, nullptr, &corrupt, &reclaimed);
  CHECK(corrupt.empty());
  CHECK(cs2.slab_slots_dead() == 0);
}

static void TestChunkStoreSlabConcurrency() {
  // compact-vs-download and compact-vs-upload at the unit level: writer
  // / reader / deleter threads race a compaction loop on a tiny-slab
  // store.  TSan + FDFS_LOCKRANK builds are the real assertion here;
  // wrong_bytes pins byte-identical reads throughout.
  std::string dir = TempDir();
  SlabOptions so;
  so.chunk_threshold = 64 << 10;
  so.slab_bytes = 1 << 20;  // clamp floor: rolls often under churn
  so.compact_min_dead_pct = 1;
  ChunkStore cs(dir, 0, 1 << 20, so);
  cs.RebuildFromRecipes();
  constexpr int kChunks = 64;
  std::vector<std::string> payloads, digs;
  for (int i = 0; i < kChunks; ++i) {
    payloads.push_back(std::string(3000 + 131 * i,
                                   static_cast<char>('a' + i % 26)));
    digs.push_back(Sha1(payloads[i].data(), payloads[i].size()).Hex());
  }
  std::atomic<bool> stop{false};
  std::atomic<int> wrong_bytes{0};
  auto churn = [&](unsigned seed) {
    unsigned s = seed;
    while (!stop.load()) {
      int i = static_cast<int>(rand_r(&s) % kChunks);
      bool existed = false;
      std::string err;
      if (!cs.PutAndRef(digs[i], payloads[i].data(), payloads[i].size(),
                        &existed, &err))
        wrong_bytes.fetch_add(1);
      Recipe r;
      r.chunks.push_back({digs[i], static_cast<int64_t>(
                                       payloads[i].size())});
      if (rand_r(&s) % 2) cs.UnrefAll(r);
    }
  };
  auto reader = [&] {
    std::string back;
    unsigned s = 99;
    while (!stop.load()) {
      int i = static_cast<int>(rand_r(&s) % kChunks);
      Recipe r;
      r.chunks.push_back({digs[i], static_cast<int64_t>(
                                       payloads[i].size())});
      cs.PinRecipe(r);
      if (cs.Has(digs[i]) &&
          cs.ReadChunk(digs[i],
                       static_cast<int64_t>(payloads[i].size()), &back) &&
          back != payloads[i])
        wrong_bytes.fetch_add(1);
      cs.UnpinRecipe(r);
    }
  };
  auto compactor = [&] {
    while (!stop.load()) {
      std::vector<ChunkStore::ChunkInfo> corrupt;
      int64_t reclaimed = 0;
      cs.CompactSlabs(nullptr, [&] { return stop.load(); }, &corrupt,
                      &reclaimed);
      if (!corrupt.empty()) wrong_bytes.fetch_add(1);
      usleep(1000);
    }
  };
  std::vector<std::thread> ts;
  ts.emplace_back(churn, 7u);
  ts.emplace_back(churn, 11u);
  ts.emplace_back(reader);
  ts.emplace_back(reader);
  ts.emplace_back(compactor);
  usleep(400 * 1000);
  stop = true;
  for (auto& t : ts) t.join();
  CHECK(wrong_bytes.load() == 0);
  // Quiesced sanity: every live digest still reads byte-identical.
  std::string back;
  for (int i = 0; i < kChunks; ++i) {
    if (cs.Has(digs[i]))
      CHECK(cs.ReadChunk(digs[i],
                         static_cast<int64_t>(payloads[i].size()), &back) &&
            back == payloads[i]);
  }
  CHECK(cs.slab_slots_live() >= 0 && cs.slab_bytes_dead() >= 0);
}

static void TestChunkStoreStripedConcurrency() {
  // Hammer the striped store from four mutator families at once —
  // uploads/deletes, pin/unpin sessions, cached reads, and a
  // scrub-style quarantine/sweep loop.  Run under TSan via
  // tools/run_sanitizers.sh; the invariant checks at the end catch
  // lost-update bugs even in an uninstrumented build.
  std::string dir = ChunkStoreDir();
  ChunkStore cs(dir, 0, /*read_cache_bytes=*/1 << 20);
  constexpr int kChunks = 32;
  std::vector<std::string> payloads, digs;
  for (int i = 0; i < kChunks; ++i) {
    payloads.push_back(std::string(2048, 'A') + std::to_string(i));
    digs.push_back(Sha1HexOf(payloads.back()));
  }
  std::atomic<bool> stop{false};
  std::atomic<int64_t> wrong_bytes{0};
  auto churn = [&](unsigned seed) {
    unsigned r = seed;
    bool existed;
    std::string err;
    while (!stop.load()) {
      int i = static_cast<int>(r = r * 1103515245 + 12345) % kChunks;
      if (i < 0) i += kChunks;
      CHECK(cs.PutAndRef(digs[i], payloads[i].data(), payloads[i].size(),
                         &existed, &err));
      Recipe one;
      one.chunks.push_back(
          {digs[i], static_cast<int64_t>(payloads[i].size())});
      cs.UnrefAll(one);
    }
  };
  auto reader = [&] {
    unsigned r = 7;
    while (!stop.load()) {
      int i = static_cast<int>(r = r * 1103515245 + 12345) % kChunks;
      if (i < 0) i += kChunks;
      bool hit = false;
      auto p = cs.ReadChunkCached(digs[i],
                                  static_cast<int64_t>(payloads[i].size()),
                                  &hit);
      // A concurrent delete may legitimately make the read fail; bytes
      // that DO come back must be exact (the zero-wrong-bytes bar).
      if (p != nullptr && *p != payloads[i]) wrong_bytes++;
    }
  };
  auto pinner = [&] {
    Recipe all;
    for (int i = 0; i < kChunks; ++i)
      all.chunks.push_back(
          {digs[i], static_cast<int64_t>(payloads[i].size())});
    while (!stop.load()) {
      std::string need = cs.PinAndMask(all);
      CHECK(need.size() == static_cast<size_t>(kChunks));
      cs.UnpinRecipe(all);
    }
  };
  auto sweeper = [&] {
    while (!stop.load()) {
      int64_t bytes = 0;
      cs.GcSweep(time(nullptr) + 10, &bytes);
      for (int i = 0; i < kChunks; i += 5) (void)cs.Quarantine(digs[i]);
      (void)cs.SnapshotLive();
      (void)cs.unique_chunks();
    }
  };
  std::vector<std::thread> ts;
  ts.emplace_back(churn, 1u);
  ts.emplace_back(churn, 2u);
  ts.emplace_back(reader);
  ts.emplace_back(reader);
  ts.emplace_back(pinner);
  ts.emplace_back(sweeper);
  usleep(400 * 1000);
  stop = true;
  for (auto& t : ts) t.join();
  CHECK(wrong_bytes.load() == 0);
  // Quiesced: accounting must be internally consistent.
  CHECK(cs.unique_chunks() >= 0);
  CHECK(cs.gc_pending_chunks() == 0);  // eager mode, nothing pinned now
  CHECK(cs.cache_bytes() <= (1 << 20));
  // Every digest is either live-and-readable or fully gone.
  for (int i = 0; i < kChunks; ++i) {
    std::string back;
    if (cs.Has(digs[i]) && !cs.IsQuarantined(digs[i]))
      CHECK(cs.ReadChunk(digs[i], static_cast<int64_t>(payloads[i].size()),
                         &back) &&
            back == payloads[i]);
  }
}

static void TestRsCodecKillAnyM() {
  // RS(k, m) must survive EVERY combination of m shard losses, not a
  // lucky subset — walk all C(k+m, m) loss patterns for a small
  // geometry and a couple of ragged lengths.
  const int k = 4, m = 2;
  for (int64_t shard_len : {int64_t{1}, int64_t{31}, int64_t{256}}) {
    std::vector<std::string> data;
    for (int i = 0; i < k; ++i) {
      std::string s(static_cast<size_t>(shard_len), '\0');
      for (int64_t b = 0; b < shard_len; ++b)
        s[static_cast<size_t>(b)] =
            static_cast<char>((i * 131 + b * 29 + 7) & 0xFF);
      data.push_back(std::move(s));
    }
    std::vector<std::string> parity = RsEncode(data, m);
    CHECK(static_cast<int>(parity.size()) == m);
    std::vector<std::string> full = data;
    for (auto& p : parity) full.push_back(p);
    for (int a = 0; a < k + m; ++a) {
      for (int b = a + 1; b < k + m; ++b) {
        std::vector<std::string> shards = full;
        shards[a].clear();
        shards[b].clear();
        CHECK(RsReconstruct(&shards, k, m, shard_len));
        for (int i = 0; i < k + m; ++i) CHECK(shards[i] == full[i]);
      }
    }
    // m + 1 losses must FAIL, not fabricate bytes.
    std::vector<std::string> shards = full;
    shards[0].clear();
    shards[2].clear();
    shards[5].clear();
    CHECK(!RsReconstruct(&shards, k, m, shard_len));
  }
}

static void TestEcStoreStripeLifecycle() {
  std::string dir = TempDir();
  std::vector<std::pair<std::string, std::string>> chunks;
  for (int i = 0; i < 3; ++i) {
    std::string pay(200 + 37 * i, static_cast<char>('p' + i));
    chunks.emplace_back(Sha1HexOf(pay), pay);
  }
  int64_t id = -1;
  {
    EcStore ec(dir, 3, 2);
    std::string err;
    id = ec.EncodeStripe(chunks, &err);
    CHECK(id >= 0);
    CHECK(ec.VerifyStripe(id, &err));
    CHECK(ec.stripes() == 1);
    CHECK(ec.stripe_chunks() == 3);
    for (auto& c : chunks) {
      std::string out;
      CHECK(ec.Has(c.first));
      CHECK(ec.ReadChunk(c.first, &out) && out == c.second);
      // Positional read across the whole payload and a mid slice.
      std::string slice(5, '\0');
      CHECK(ec.ReadChunkSlice(c.first, 3, 5, slice.data()));
      CHECK(slice == c.second.substr(3, 5));
    }
  }
  // Cold restart adopts the stripe from the manifest alone.
  EcStore ec(dir, 3, 2);
  CHECK(ec.Rescan() == 1);
  CHECK(ec.stripe_chunks() == 3);
  // Corrupt one shard payload in place: the scrub repair must detect
  // it via CRC and rebuild it from parity, in place.
  {
    char shard[64];
    snprintf(shard, sizeof(shard), "/%010lld.s01", (long long)id);
    FlipFirstByte(dir + shard);  // header magic => header CRC fail
  }
  std::vector<EcStore::ChunkRef> lost;
  int64_t rebuilt = 0, rb = 0, rd = 0;
  CHECK(ec.VerifyRepairStripe(id, &lost, &rebuilt, &rb, &rd) ==
        EcStore::StripeHealth::kRepaired);
  CHECK(rebuilt == 1 && rb > 0);
  CHECK(ec.VerifyRepairStripe(id, &lost, &rebuilt, &rb, &rd) ==
        EcStore::StripeHealth::kHealthy);
  // Lose MORE than m shards: kLost must list the live chunks so the
  // caller can re-promote them, and DropStripe reclaims the carcass.
  for (int s = 0; s < 3; ++s) {
    char shard[64];
    snprintf(shard, sizeof(shard), "/%010lld.s%02d", (long long)id, s);
    unlink((dir + shard).c_str());
  }
  lost.clear();
  CHECK(ec.VerifyRepairStripe(id, &lost, &rebuilt, &rb, &rd) ==
        EcStore::StripeHealth::kLost);
  CHECK(lost.size() == 3);
  int64_t reclaimed = 0;
  ec.DropStripe(id, &reclaimed);
  CHECK(ec.stripes() == 0);
  CHECK(!ec.Has(chunks[0].first));

  // MarkDead reclaims the whole stripe when its last live chunk dies.
  std::string err;
  int64_t id2 = ec.EncodeStripe(chunks, &err);
  CHECK(id2 >= 0);
  int64_t freed = 0;
  CHECK(ec.MarkDead(chunks[0].first, &freed) && freed == 0);
  CHECK(ec.MarkDead(chunks[1].first, &freed) && freed == 0);
  CHECK(ec.MarkDead(chunks[2].first, &freed));
  CHECK(freed > 0);  // parity included
  CHECK(ec.stripes() == 0);

  // release.map: append + torn-tail-tolerant replay + clear.
  std::vector<std::pair<std::string, int64_t>> batch = {
      {chunks[0].first, 200}, {chunks[1].first, 237}};
  CHECK(ec.AppendReleaseMap(batch, &err));
  auto pending = ec.PendingReleases();
  CHECK(pending.size() == 2 && pending[1].second == 237);
  ec.ClearReleaseMap();
  CHECK(ec.PendingReleases().empty());
}

// Stripes that share a digest (two uploads committing one new chunk):
// the first keeps it and the later slot is dead on disk, so the gauges
// count it once, DropStripe of the later stripe leaves it readable, and
// MarkDead reclaims every stripe.  A manifest that still holds the
// duplicate live (a crash before its rewrite) is settled at Rescan.
static void TestEcStoreDuplicateDigestKeepsOneSlot() {
  auto chunk = [](char c, size_t n) {
    std::string p(n, c);
    return std::make_pair(Sha1HexOf(p), p);
  };
  const auto a = chunk('a', 300), b = chunk('b', 250), c = chunk('c', 120);
  const auto d = chunk('d', 90);
  std::string err;
  std::string dir = TempDir();
  {
    EcStore ec(dir, 3, 2);
    const int64_t first = ec.EncodeStripe({a, b}, &err);
    const int64_t second = ec.EncodeStripe({b, c}, &err);
    CHECK(first >= 0 && second > first);
    CHECK(ec.stripes() == 2 && ec.stripe_chunks() == 3);
    // Every digest of a third stripe is held already: it is not kept.
    CHECK(ec.EncodeStripe({a, c}, &err) >= 0);
    CHECK(ec.stripes() == 2 && ec.stripe_chunks() == 3);
    {
      EcStore again(dir, 3, 2);
      CHECK(again.Rescan() == 2 && again.stripe_chunks() == 3);
    }
    ec.DropStripe(second, nullptr);
    std::string out;
    CHECK(ec.ReadChunk(b.first, &out) && out == b.second);
    CHECK(!ec.Has(c.first));
    CHECK(ec.MarkDead(a.first, nullptr) && ec.MarkDead(b.first, nullptr));
    CHECK(ec.stripes() == 0);
    EcStore after(dir, 3, 2);
    CHECK(after.Rescan() == 0);
  }
  // The crash window: stripe 1 of another store, {b, c} with b live, is
  // moved in beside stripe 0 {a, b}.
  std::string dir2 = TempDir(), other = TempDir();
  {
    EcStore ec(dir2, 3, 2), ec_other(other, 3, 2);
    CHECK(ec.EncodeStripe({a, b}, &err) == 0);
    CHECK(ec_other.EncodeStripe({d}, &err) == 0);
    CHECK(ec_other.EncodeStripe({b, c}, &err) == 1);
  }
  for (const char* name : {"0000000001.mft", "0000000001.s00",
                           "0000000001.s01", "0000000001.s02",
                           "0000000001.s03", "0000000001.s04"})
    CHECK(rename((other + "/" + name).c_str(),
                 (dir2 + "/" + name).c_str()) == 0);
  {
    EcStore ec(dir2, 3, 2);
    CHECK(ec.Rescan() == 2 && ec.stripe_chunks() == 3);
    for (const auto& ch : {a, b, c}) {
      std::string out;
      CHECK(ec.ReadChunk(ch.first, &out) && out == ch.second);
    }
    CHECK(ec.MarkDead(b.first, nullptr));
  }
  // The duplicate slot died on disk: b does not come back.
  EcStore ec(dir2, 3, 2);
  CHECK(ec.Rescan() == 2 && ec.stripe_chunks() == 2 && !ec.Has(b.first));
  CHECK(ec.MarkDead(a.first, nullptr) && ec.MarkDead(c.first, nullptr));
  CHECK(ec.stripes() == 0);
}

static void TestChunkStoreEcDemoteReleaseRemoteRead() {
  // Owner side: demote cold chunks into a stripe, reads fall through.
  std::string owner_dir = ChunkStoreDir();
  ChunkStore owner(owner_dir, 0, 0, SlabOptions{}, /*ec_k=*/2, /*ec_m=*/1);
  CHECK(owner.ec_enabled());
  Recipe r;
  std::vector<std::string> payloads, digs;
  bool existed = false;
  std::string err;
  for (int i = 0; i < 4; ++i) {
    payloads.emplace_back(500 + i, static_cast<char>('e' + i));
    digs.push_back(Sha1HexOf(payloads.back()));
    CHECK(owner.PutAndRef(digs[i], payloads[i].data(), payloads[i].size(),
                          &existed, &err));
    r.chunks.push_back({digs[i], static_cast<int64_t>(payloads[i].size())});
    r.logical_size += static_cast<int64_t>(payloads[i].size());
  }
  CHECK(WriteRecipeFile(owner_dir + "/data/ec.rcp", r, &err));
  auto cands = owner.SnapshotDemotable(time(nullptr) + 10, 1);
  CHECK(cands.size() == 4);
  int64_t nchunks = 0, nbytes = 0;
  int64_t sid = owner.DemoteToEc(cands, &nchunks, &nbytes, &err);
  CHECK(sid >= 0);
  CHECK(nchunks == 4);
  CHECK(owner.ec_stripes() == 1);
  // The flat payloads are gone; reads decode from the stripe.
  for (int i = 0; i < 4; ++i) {
    CHECK(!FileExists(owner.ChunkPath(digs[i])));
    std::string back;
    CHECK(owner.ReadChunk(digs[i], static_cast<int64_t>(payloads[i].size()),
                          &back));
    CHECK(back == payloads[i]);
  }
  // Demoted chunks are NOT demotable again.
  CHECK(owner.SnapshotDemotable(time(nullptr) + 10, 1).empty());

  // Peer side: EC_RELEASE drops the replica, journaled; reads route to
  // the remote-fetch hook (which the server wires to FETCH_CHUNK).
  std::string peer_dir = ChunkStoreDir();
  {
    ChunkStore peer(peer_dir, 0);
    for (int i = 0; i < 4; ++i)
      CHECK(peer.PutAndRef(digs[i], payloads[i].data(), payloads[i].size(),
                           &existed, &err));
    CHECK(WriteRecipeFile(peer_dir + "/data/ec.rcp", r, &err));
    std::vector<ChunkStore::ChunkInfo> infos;
    for (int i = 0; i < 4; ++i)
      infos.push_back({digs[i], static_cast<int64_t>(payloads[i].size())});
    std::string mask = peer.ReleaseChunks(infos);
    CHECK(mask == std::string(4, '\0'));
    CHECK(peer.released_chunks() == 4);
    CHECK(peer.IsReleased(digs[0]));
    CHECK(!FileExists(peer.ChunkPath(digs[0])));
    // Releasing again is idempotent (the replayed-handover case).
    CHECK(peer.ReleaseChunks(infos) == std::string(4, '\0'));
    // No hook: the read fails clean instead of fabricating bytes.
    std::string back;
    CHECK(!peer.ReadChunk(digs[0],
                          static_cast<int64_t>(payloads[0].size()), &back));
    int fetches = 0;
    peer.set_remote_fetch([&](const std::string& dig, int64_t len,
                              std::string* out) {
      ++fetches;
      std::string got;
      if (!owner.ReadChunk(dig, len, &got)) return false;
      out->swap(got);
      return true;
    });
    CHECK(peer.ReadChunk(digs[0],
                         static_cast<int64_t>(payloads[0].size()), &back));
    CHECK(back == payloads[0] && fetches == 1);
    CHECK(peer.ec_remote_reads() == 1);
    // Slice reads work through the hook too.
    std::string slice(7, '\0');
    CHECK(peer.ReadChunkSlice(digs[1], 11, 7, slice.data()));
    CHECK(slice == payloads[1].substr(11, 7));
    // A re-uploaded payload UNRELEASES: local bytes win again.
    CHECK(peer.PutAndRef(digs[2], payloads[2].data(), payloads[2].size(),
                         &existed, &err));
    CHECK(!peer.IsReleased(digs[2]));
    CHECK(peer.released_chunks() == 3);
  }
  // Restart replays released.log: marks survive for referenced digests
  // with no local payload, and the re-uploaded chunk stays local.
  ChunkStore peer2(peer_dir, 0);
  peer2.RebuildFromRecipes();
  CHECK(peer2.released_chunks() == 3);
  CHECK(peer2.IsReleased(digs[0]) && !peer2.IsReleased(digs[2]));
  std::string back;
  CHECK(peer2.ReadChunk(digs[2], static_cast<int64_t>(payloads[2].size()),
                        &back));
  CHECK(back == payloads[2]);

  // Owner restart rescans the stripe and still serves decoded reads.
  ChunkStore owner2(owner_dir, 0, 0, SlabOptions{}, 2, 1);
  owner2.RebuildFromRecipes();
  CHECK(owner2.ec_stripes() == 1);
  CHECK(owner2.ReadChunk(digs[3], static_cast<int64_t>(payloads[3].size()),
                         &back));
  CHECK(back == payloads[3]);
  // DELETE reclaims parity: with no grace window the last unref retires
  // the chunks eagerly, and the last live chunk takes the stripe with it.
  owner2.UnrefAll(r);
  CHECK(owner2.ec_stripes() == 0);
  CHECK(owner2.ec_parity_bytes() == 0);
}

// A plugin that counts what the ingest asks of it; fingerprints fail on
// demand (one chunk a segment otherwise).
class CountingPlugin : public DedupPlugin {
 public:
  int begins = 0, segments = 0, commits = 0, aborts = 0;
  int64_t session = 0;
  std::string committed_to;
  bool fail_fingerprint = false;
  Verdict Judge(const std::string&, int64_t) override { return {}; }
  void Commit(const std::string&, const std::string&) override {}
  void Forget(const std::string&) override {}
  const char* Name() const override { return "counting"; }
  int64_t BeginChunked() override { return ++begins; }
  bool FingerprintChunks(int64_t s, const char* data, size_t len,
                         int64_t base, std::vector<ChunkFp>* out) override {
    ++segments;
    session = s;
    if (fail_fingerprint) return false;
    out->push_back({base, static_cast<int64_t>(len),
                    Sha1(data, len).Hex()});
    return true;
  }
  void CommitChunked(int64_t s, const std::string& ref) override {
    ++commits;
    session = s;
    committed_to = ref;
  }
  void AbortChunked(int64_t s) override {
    ++aborts;
    session = s;
  }
};

// Every exit of a caller ends the session: an early return aborts it, a
// commit commits it once and aborts nothing.
static void TestFingerprintSessionCannotLeak() {
  CountingPlugin p;
  auto ingest = [&p](bool bail) {
    FingerprintSession s(&p, /*reindex=*/true);
    std::vector<ChunkFp> fps;
    if (!s.Segment("abc", 3, 0, &fps) || bail) return false;
    s.Commit("group1/M00/00/00/f");
    return true;
  };
  CHECK(!ingest(true));
  CHECK(p.begins == 1 && p.segments == 1 && p.aborts == 1 && p.commits == 0);
  CHECK((p.session & kDedupReindexSessionBit) != 0);
  CHECK(ingest(false));
  CHECK(p.begins == 2 && p.aborts == 1 && p.commits == 1);
  CHECK(p.committed_to == "group1/M00/00/00/f");
  // The upload's own session: a fingerprint that fails mid-stream ends
  // in the flat fallback with the session aborted, not left pending.
  std::string dir = TempDir();
  ChunkStore cs(dir, 0);
  std::string tmp = dir + "/upload.tmp";
  std::string body(5000, 'u');
  FILE* f = std::fopen(tmp.c_str(), "wb");
  std::fwrite(body.data(), 1, body.size(), f);
  std::fclose(f);
  IngestTarget t;
  t.cs = &cs;
  t.plugin = &p;
  t.segment_bytes = 2048;
  int64_t saved = 0, hits = 0;
  p.fail_fingerprint = true;
  CHECK(!StoreChunked(t, tmp, 5000, dir + "/a.rcp", "g/a", &saved, &hits));
  CHECK(p.begins == 3 && p.aborts == 2 && p.commits == 1);
  CHECK((p.session & kDedupReindexSessionBit) == 0);
  p.fail_fingerprint = false;
  CHECK(StoreChunked(t, tmp, 5000, dir + "/b.rcp", "g/b", &saved, &hits));
  CHECK(p.begins == 4 && p.segments == 6 && p.aborts == 2 && p.commits == 2);
  CHECK(p.committed_to == "g/b" && cs.HasRecipe(dir + "/b.rcp"));
  CHECK(!cs.HasRecipe(dir + "/a.rcp"));
}

// A chunk that is not its digest is refused before the store sees it:
// no reference, nothing in the rollback set.
static void TestAdmitChunkRefusesWhatIsNotItsDigest() {
  std::string dir = TempDir();
  ChunkStore cs(dir, 0);
  std::string good(3000, 'g'), bad(3000, 'b');
  const RecipeEntry e{Sha1(good.data(), good.size()).Hex(), 3000};
  Recipe taken;
  taken.chunks.push_back({"0000000000000000000000000000000000000000", 1});
  std::string err;
  CHECK(AdmitChunk(&cs, e, bad.data(), &taken, &err) ==
        Admission::kNotItsDigest);
  CHECK(taken.chunks.size() == 1);
  CHECK(!cs.Has(e.digest_hex));
  CHECK(AdmitChunk(&cs, e, good.data(), &taken, &err) ==
        Admission::kAdmitted);
  CHECK(taken.chunks.size() == 2 && taken.chunks[1].digest_hex == e.digest_hex);
  CHECK(cs.Has(e.digest_hex));
  std::string back;
  CHECK(cs.ReadChunk(e.digest_hex, 3000, &back) && back == good);
}

// The recipe walk hands over each segment's bytes exactly, entries that
// cross a segment end and a digest repeated within one segment included,
// from flat and slab-resident chunks alike.
static void TestWalkRecipeAssemblesSegments() {
  for (int slab = 0; slab < 2; ++slab) {
    std::string dir = TempDir();
    SlabOptions so;
    if (slab) {
      so.chunk_threshold = 64 * 1024;
      so.recipe_threshold = 4096;
      so.slab_bytes = 1 << 20;
    }
    ChunkStore cs(dir, 0, 0, so);
    cs.RebuildFromRecipes();
    std::string a(3000, '\0'), b(5000, '\0');
    for (size_t i = 0; i < a.size(); ++i) a[i] = static_cast<char>(i * 7);
    for (size_t i = 0; i < b.size(); ++i) b[i] = static_cast<char>(i * 13 + 1);
    const std::string da = Sha1(a.data(), a.size()).Hex();
    const std::string db = Sha1(b.data(), b.size()).Hex();
    bool existed = false;
    std::string err;
    CHECK(cs.PutAndRef(da, a.data(), a.size(), &existed, &err));
    CHECK(cs.PutAndRef(db, b.data(), b.size(), &existed, &err));
    Recipe r;
    std::string want;
    for (int k : {0, 1, 0, 0, 1}) {
      r.chunks.push_back({k ? db : da, k ? 5000 : 3000});
      want += k ? b : a;
    }
    r.logical_size = static_cast<int64_t>(want.size());
    for (int64_t seg : {int64_t{7000}, int64_t{1} << 20}) {
      std::string got;
      std::vector<int64_t> bases;
      CHECK(WalkRecipe(&cs, r, seg, [&](int64_t base, char* p, int64_t len) {
        bases.push_back(base);
        CHECK(len == std::min(seg, r.logical_size - base));
        got.append(p, static_cast<size_t>(len));
        return true;
      }));
      CHECK(got == want);
      CHECK(bases.size() ==
            static_cast<size_t>((r.logical_size + seg - 1) / seg));
    }
    // A chunk the store does not hold fails the walk.
    Recipe gone = r;
    gone.chunks[3].digest_hex = std::string(40, 'f');
    CHECK(!WalkRecipe(&cs, gone, 7000,
                      [](int64_t, char*, int64_t) { return true; }));
  }
}

int main() {
  TestBinlogRecordCodec();
  TestBinlogWriteReadResume();
  TestBinlogRotation();
  TestCpuDedup();
  TestDedupRecordsIntoBoundStageTrace();
  TestStoreInit();
  TestTrunkAllocator();
  TestTrunkReserveAndCompaction();
  TestTrunkReplicaWrite();
  TestChunkStoreGcGraceAndPins();
  TestChunkStoreEagerModeUnchanged();
  TestChunkStoreQuarantineRepairHeal();
  TestChunkStoreRebuildParksOrphansAndKeepsQuarantine();
  TestChunkStoreReadRecipeAndPinRange();
  TestChunkStoreReadCacheCoherence();
  TestSlabRecordCodec();
  TestSlabStoreAppendRescanCompact();
  TestChunkStoreSlabEndToEnd();
  TestChunkStoreSlabConcurrency();
  TestChunkStoreStripedConcurrency();
  TestRsCodecKillAnyM();
  TestEcStoreStripeLifecycle();
  TestEcStoreDuplicateDigestKeepsOneSlot();
  TestChunkStoreEcDemoteReleaseRemoteRead();
  TestFingerprintSessionCannotLeak();
  TestAdmitChunkRefusesWhatIsNotItsDigest();
  TestWalkRecipeAssemblesSegments();
  if (g_failures == 0) {
    std::printf("storage_test: ALL PASS\n");
    return 0;
  }
  std::printf("storage_test: %d FAILURES\n", g_failures);
  return 1;
}
