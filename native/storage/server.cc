#include "storage/server.h"

#include <errno.h>
#include <fcntl.h>
#include <string.h>
#include <sys/sendfile.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/statvfs.h>
#include <sys/time.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

#include <cstdio>

#include "common/fileid.h"
#include "common/fsutil.h"
#include "common/healthmon.h"
#include "common/log.h"
#include "common/profiler.h"
#include "common/protocol_gen.h"
#include "common/threadreg.h"
#include "storage/ingest.h"

namespace fdfs {

namespace {

// kMaxInlineBody (the non-streamed body cap) comes from protocol_gen.h:
// it is a wire contract shared with senders (sync.cc sizes the
// chunk-aware replication messages against it).
constexpr int64_t kBinlogRotateSize = 64LL << 20;
constexpr size_t kIoBufSize = 256 * 1024;

// SHA-1 of the first `size` bytes of `path`, a segment at a time in the
// kept buffer; nullopt when they cannot be read or the file is shorter.
std::optional<Sha1Digest> Sha1OfFile(const std::string& path, int64_t size,
                                     int64_t segment_bytes) {
  int fd = open(path.c_str(), O_RDONLY);
  if (fd < 0) return std::nullopt;
  Sha1Stream sha1;
  const bool ok = WalkSegments(size, segment_bytes,
                               [&](int64_t base, char* seg, int64_t len) {
    if (!PreadFull(fd, seg, len, base)) return false;
    sha1.Update(seg, static_cast<size_t>(len));
    return true;
  });
  close(fd);
  if (!ok) return std::nullopt;
  return sha1.Final();
}

// Per-chunk payload cap, shared by FETCH_CHUNK serving and the
// SYNC_CREATE_RECIPE entry validation: no single declared chunk may make
// a dio worker allocate more than this.
constexpr int64_t kMaxChunkPayload = 8 << 20;

std::string GroupFromField(const uint8_t* p) {
  size_t n = 0;
  while (n < static_cast<size_t>(kGroupNameMaxLen) && p[n] != 0) ++n;
  return std::string(reinterpret_cast<const char*>(p), n);
}

std::string ExtFromField(const uint8_t* p) {
  size_t n = 0;
  while (n < static_cast<size_t>(kFileExtNameMaxLen) && p[n] != 0) ++n;
  return std::string(reinterpret_cast<const char*>(p), n);
}

std::string PackGroupField(const std::string& group) {
  std::string out(kGroupNameMaxLen, '\0');
  memcpy(out.data(), group.data(),
         std::min(group.size(), static_cast<size_t>(kGroupNameMaxLen)));
  return out;
}

// Atomic metadata-sidecar write (tmp + rename).  A partial write must not
// report success: the sync sender advances its mark on status 0 and never
// retries.
bool WriteSidecarAtomic(const std::string& meta_path, const std::string& meta) {
  std::string tmp = meta_path + ".tmp";
  FILE* f = fopen(tmp.c_str(), "w");
  if (f == nullptr) return false;
  bool ok = fwrite(meta.data(), 1, meta.size(), f) == meta.size();
  ok = (fclose(f) == 0) && ok;
  if (!ok || rename(tmp.c_str(), meta_path.c_str()) != 0) {
    unlink(tmp.c_str());
    return false;
  }
  return true;
}

}  // namespace

StorageServer::StorageServer(StorageConfig cfg) : cfg_(std::move(cfg)) {}

StorageServer::~StorageServer() {
  for (auto& t : nio_) {
    for (auto& [fd, c] : t->conns) {
      if (c->file_fd >= 0) close(c->file_fd);
      if (c->send_fd >= 0) close(c->send_fd);
      close(fd);
    }
    if (t->listen_fd >= 0) close(t->listen_fd);
  }
  if (listen_fd_ >= 0) close(listen_fd_);
}

bool StorageServer::Init(std::string* error) {
  if (!MakeDirs(cfg_.base_path + "/data") || !MakeDirs(cfg_.base_path + "/logs")) {
    *error = "cannot create base_path dirs under " + cfg_.base_path;
    return false;
  }
  if (!store_.Init(cfg_, error)) return false;
  if (!binlog_.Init(cfg_.base_path + "/data/sync", kBinlogRotateSize, error))
    return false;
  // Flight recorder FIRST: every subsystem below may record into it
  // (chunk-store heals, scrub quarantines, sync stalls, config clamps).
  events_ = std::make_unique<EventLog>(
      static_cast<size_t>(cfg_.event_buffer_size));
  for (const std::string& a : cfg_.anomalies)
    events_->Record(EventSeverity::kWarn, "config.anomaly", a);
  // Telemetry history + SLOs + heat (ISSUE 8).  The journal opens (and
  // recovers its torn tail) before the first tick; a failed open logs
  // and disables journaling rather than killing the daemon —
  // observability must never take the data path down with it.
  if (cfg_.metrics_journal_mb > 0 && cfg_.slo_eval_interval_s > 0) {
    metrics_ = std::make_unique<MetricsJournal>(
        cfg_.base_path + "/data/metrics",
        static_cast<int64_t>(cfg_.metrics_journal_mb) << 20);
    std::string merr;
    if (!metrics_->Open(&merr)) {
      FDFS_LOG_WARN("metrics journal disabled: %s", merr.c_str());
      events_->Record(EventSeverity::kWarn, "config.anomaly",
                      "metrics journal disabled", merr);
      metrics_.reset();
    }
  }
  if (cfg_.slo_eval_interval_s > 0) {
    std::vector<SloRule> rules;
    if (!cfg_.slo_rules_file.empty()) {
      IniConfig slo_ini;
      std::string serr;
      if (slo_ini.LoadFile(cfg_.slo_rules_file, &serr)) {
        rules = SloEvaluator::LoadRules(slo_ini);
      } else {
        // A missing/bad override file falls back to defaults LOUDLY: an
        // operator who wrote rules must not silently run without them.
        FDFS_LOG_WARN("slo_rules_file %s: %s (using compiled-in defaults)",
                      cfg_.slo_rules_file.c_str(), serr.c_str());
        events_->Record(EventSeverity::kWarn, "config.anomaly",
                        "slo_rules_file unreadable", serr);
        rules = SloEvaluator::DefaultRules();
      }
    } else {
      rules = SloEvaluator::DefaultRules();
    }
    slo_ = std::make_unique<SloEvaluator>(std::move(rules), events_.get());
  }
  if (cfg_.heat_top_k > 0)
    heat_ = std::make_unique<HeatSketch>(cfg_.heat_top_k);
  // Admission control (ISSUE 19): always constructed — with
  // admission_control = 0 the controller still classifies and counts
  // every request (ADMISSION_STATUS and the admission.* gauges stay
  // live for triage) but never sheds.
  {
    AdmissionConfig acfg;
    acfg.enabled = cfg_.admission_control;
    acfg.tighten_threshold = cfg_.admission_tighten_pct / 100.0;
    acfg.relax_threshold = cfg_.admission_relax_pct / 100.0;
    acfg.queue_depth_high = cfg_.admission_queue_depth_high;
    acfg.loop_lag_high_ms =
        static_cast<double>(cfg_.admission_loop_lag_high_ms);
    acfg.inflight_high_bytes = cfg_.admission_inflight_high_bytes;
    acfg.retry_after_ms = cfg_.admission_retry_after_ms;
    admission_ = std::make_unique<AdmissionController>(acfg);
  }
  // The dio pools' size is fixed here, at start (workers.h has the
  // rule), because the fingerprint plugin's idle connections follow it:
  // every worker may be inside the sidecar at once, and a connection
  // closed for want of room costs the next RPC a new sidecar thread and
  // a cold receive buffer.
  dio_workers_per_path_ =
      DioWorkersPerPath(cfg_.disk_writer_threads,
                        std::thread::hardware_concurrency(),
                        store_.store_path_count());
  dedup_ = MakeDedupPlugin(cfg_.dedup_mode, cfg_.base_path, cfg_.dedup_sidecar,
                           dio_workers_per_path_ * store_.store_path_count(),
                           cfg_.cdc_widths);
  if (dedup_ != nullptr && cfg_.dedup_chunk_threshold > 0) {
    // Chunk-level dedup: one content-addressed store per store path;
    // refcounts rebuilt from recipes (doubles as orphan GC).
    SlabOptions sopts;
    sopts.chunk_threshold = cfg_.slab_chunk_threshold;
    sopts.recipe_threshold = cfg_.slab_recipe_threshold;
    sopts.slab_bytes = static_cast<int64_t>(cfg_.slab_size_mb) << 20;
    sopts.compact_min_dead_pct = cfg_.slab_compact_min_dead_pct;
    for (int i = 0; i < store_.store_path_count(); ++i) {
      chunk_stores_.push_back(std::make_unique<ChunkStore>(
          store_.store_path(i), cfg_.chunk_gc_grace_s,
          static_cast<int64_t>(cfg_.read_cache_mb) << 20, sopts,
          cfg_.ec_k, cfg_.ec_m));
      chunk_stores_.back()->set_events(events_.get());
      chunk_stores_.back()->RebuildFromRecipes();
      // Released chunks (EC cold tier): the replica lives with the
      // stripe's owner now — reads round-robin the group peers via
      // FETCH_CHUNK (the owner's ReadChunk falls through to its EC
      // stripes, so the bytes come back decoded + SHA1-gated).
      chunk_stores_.back()->set_remote_fetch(
          [this, i](const std::string& digest_hex, int64_t len,
                    std::string* out) {
            return FetchChunkFromPeers(i, digest_hex, len, out);
          });
    }
  }

  // nio work threads + per-store-path dio pools (reference:
  // storage_nio.c / storage_dio.c; storage.conf:work_threads,
  // disk_writer_threads).  Loops are created here, threads start in
  // Run().
  for (int i = 0; i < cfg_.work_threads; ++i) {
    auto t = std::make_unique<NioThread>();
    t->loop = std::make_unique<EventLoop>();
    nio_.push_back(std::move(t));
  }

  // Sharded accept (ISSUE 18): one SO_REUSEPORT listener per reactor,
  // each added to its loop BEFORE the thread starts (EventLoop::Add is
  // safe pre-Run).  All listeners of the port must carry the flag, so
  // a refusal on ANY of them unwinds the whole group and falls back to
  // the single main-loop acceptor + round-robin handoff.
  if (cfg_.nio_reuseport && !nio_.empty()) {
    std::string rp_err;
    for (auto& t : nio_) {
      t->listen_fd = TcpListenReuseport(cfg_.bind_addr, cfg_.port, &rp_err);
      if (t->listen_fd < 0) break;
      SetNonBlocking(t->listen_fd);
    }
    if (nio_.back()->listen_fd >= 0) {
      reuseport_active_ = true;
      for (auto& t : nio_) {
        NioThread* raw = t.get();
        t->loop->Add(raw->listen_fd, EPOLLIN,
                     [this, raw](uint32_t) { OnReactorAccept(raw); });
      }
    } else {
      for (auto& t : nio_) {
        if (t->listen_fd >= 0) close(t->listen_fd);
        t->listen_fd = -1;
      }
      FDFS_LOG_WARN("nio_reuseport: kernel refused (%s); "
                    "falling back to single-acceptor round-robin",
                    rp_err.c_str());
    }
  }
  if (!reuseport_active_) {
    listen_fd_ = TcpListen(cfg_.bind_addr, cfg_.port, error);
    if (listen_fd_ < 0) return false;
    SetNonBlocking(listen_fd_);
    loop_.Add(listen_fd_, EPOLLIN, [this](uint32_t ev) { OnAccept(ev); });
  }
  for (int i = 0; i < store_.store_path_count(); ++i)
    dio_pools_.push_back(std::make_unique<WorkerPool>(
        dio_workers_per_path_, "dio.worker", i * dio_workers_per_path_));

  // Trace ring before the registry (its gauges read the ring) and before
  // the sync/recovery subsystems (they record spans into it).
  trace_ = std::make_unique<TraceRing>(
      static_cast<size_t>(cfg_.trace_buffer_size));

  // Stats registry before any subsystem that feeds it: handlers and the
  // beat callback only touch pre-registered atomic pointers.
  InitStatsRegistry();

  // Gray-failure health layer (common/healthmon.h): install the passive
  // NetRpc observer before any subsystem that makes outbound RPCs
  // starts — reporter beats, sync ships, scrub/recovery FETCH_*, EC
  // fan-out all funnel through NetRpc, so from here on every one of
  // them feeds the per-peer health table for free.  The peer.rpc_us
  // histogram was registered by InitStatsRegistry just above.
  HealthMonitor::InstallRpcObserver();
  HealthMonitor::Global().SetRpcHistogram(hist_peer_rpc_);

  // Profiler ceiling (0 keeps the feature entirely off: no handler, no
  // slab); the singleton is process-global like SIGPROF itself.
  Profiler::Global().set_max_hz(cfg_.profile_max_hz);

  // Saturation telemetry (ISSUE 6): every nio event loop observes its
  // per-iteration callback time into one shared loop-lag histogram (the
  // stall a slow handler inflicts on every other conn of its loop), and
  // the per-store-path dio pools observe queue wait + service time.
  // Each loop also accumulates its own busy time so the metrics tick
  // can publish a per-loop duty cycle (nio.loop_busy_pct.<i>) — the
  // signal the shared lag histogram cannot attribute to one loop.
  auto make_hook = [this](std::atomic<int64_t>* busy) {
    return [this, busy](int64_t busy_us, int n_events) {
      hist_nio_lag_->Observe(busy_us);
      busy->fetch_add(busy_us, std::memory_order_relaxed);
      if (n_events > 0)
        ctr_nio_dispatched_->fetch_add(n_events, std::memory_order_relaxed);
    };
  };
  loop_.set_iteration_hook(make_hook(&main_loop_busy_us_));  // accept+timers
  for (auto& t : nio_) t->loop->set_iteration_hook(make_hook(&t->busy_us));
  loop_busy_last_.assign(nio_.size() + 1, 0);
  for (auto& pool : dio_pools_)
    pool->SetStats(hist_dio_wait_, hist_dio_service_);

  if (!cfg_.tracker_servers.empty()) {
    // Sync manager first: the reporter's peer lists drive its thread pool.
    SyncCallbacks scbs;
    scbs.resolve_local = [this](const std::string& remote) {
      return ResolveLocal(cfg_.group_name, remote);
    };
    scbs.report = [this](const std::string& ip, int port, int64_t ts) {
      if (reporter_ != nullptr) reporter_->ReportSyncProgress(ip, port, ts);
    };
    scbs.binlog_quiescent = [this]() { return binlog_.Quiescent(); };
    // Shared by the sync replayer and the hot-replication fan-out
    // worker: both ship logical bytes (trunk slots and chunk recipes
    // materialize; the receiver re-chunks under its own config).
    std::function<std::optional<ContentHandle>(const std::string&)>
        open_content_fn =
            [this](const std::string& remote) -> std::optional<ContentHandle> {
      auto parts = DecodeFileId(cfg_.group_name + "/" + remote);
      if (parts.has_value() && parts->trunk_loc.has_value()) {
        const TrunkLocation& loc = *parts->trunk_loc;
        std::string path = TrunkFilePath(store_.store_path(0), loc.trunk_id);
        int fd = open(path.c_str(), O_RDONLY);
        if (fd < 0) return std::nullopt;
        auto h = ReadSlotHeader(fd, loc.offset);
        if (!h.has_value() || h->type != kTrunkSlotData ||
            h->alloc_size != loc.alloc_size ||
            h->file_size != parts->file_size ||
            h->crc32 != parts->crc32) {
          close(fd);
          return std::nullopt;
        }
        ContentHandle out;
        out.fd = fd;
        out.offset = loc.offset + kTrunkHeaderSize;
        out.size = h->file_size;
        return out;
      }
      std::string local = ResolveLocal(cfg_.group_name, remote);
      if (local.empty()) return std::nullopt;
      // Logical open: plain file, or chunk recipe materialized into an
      // unlinked temp fd — replication always ships logical bytes (the
      // peer re-chunks under its own dedup config).
      int64_t size = 0;
      int fd = OpenLogical(local, &size);
      if (fd < 0) return std::nullopt;
      ContentHandle out;
      out.fd = fd;
      out.size = size;
      return out;
    };
    scbs.open_content = open_content_fn;
    // Chunk-aware replication hooks: recipe-stored files ship their
    // recipe + only-missing chunks to peers instead of logical bytes.
    scbs.pin_recipe =
        [this](const std::string& remote) -> std::optional<Recipe> {
      std::string local = ResolveLocal(cfg_.group_name, remote);
      if (local.empty()) return std::nullopt;
      ChunkStore* cs = StoreForLocal(local);
      if (cs == nullptr) return std::nullopt;
      return cs->ReadRecipeAndPin(local + ".rcp");
    };
    scbs.unpin_recipe = [this](const std::string& remote, const Recipe& r) {
      std::string local = ResolveLocal(cfg_.group_name, remote);
      ChunkStore* cs = local.empty() ? nullptr : StoreForLocal(local);
      if (cs != nullptr) cs->UnpinRecipe(r);
    };
    scbs.read_chunk = [this](const std::string& remote,
                             const std::string& digest_hex, int64_t len,
                             std::string* out) {
      std::string local = ResolveLocal(cfg_.group_name, remote);
      ChunkStore* cs = local.empty() ? nullptr : StoreForLocal(local);
      return cs != nullptr && cs->ReadChunk(digest_hex, len, out);
    };
    // Trace stitching across the replication hop: the sender consumes
    // the traced-mutation context for each record it ships (prefixing a
    // TRACE_CTX frame so the peer's replay spans join the trace) and
    // records its own sync.ship span here.
    scbs.trace_corr = &trace_corr_;
    scbs.trace_ring = trace_.get();
    scbs.events = events_.get();
    sync_ = std::make_unique<SyncManager>(cfg_, std::move(scbs));
    reporter_ = std::make_unique<TrackerReporter>(
        cfg_, [this](int64_t* out) { FillBeatStats(out); },
        [this](const std::vector<PeerInfo>& peers) {
          sync_->UpdatePeers(peers);
        });
    // Health trailer: every beat carries this node's gray score + its
    // view of its peers, in the append-only region past the pinned stat
    // slots — the tracker folds all reporters' trailers into the N x N
    // HEALTH_MATRIX.
    reporter_->set_health_trailer_fn(
        [] { return HealthMonitor::Global().PackBeatTrailer(); });
    // Heat trailer (ISSUE 20): the sketch's cumulative download
    // counters ride every beat after the health trailer; the tracker
    // windows them per node (reset-clamped), so the wire stays
    // stateless and beat loss only costs freshness.
    reporter_->set_heat_trailer_fn([this]() -> std::string {
      if (heat_ == nullptr) return std::string();
      std::vector<HeatTrailerEntry> entries;
      for (const auto& t : heat_->Top(cfg_.heat_top_k)) {
        int op = static_cast<int>(HeatOp::kDownload);
        if (t.op_count[op] <= 0) continue;
        HeatTrailerEntry he;
        he.key = t.key;
        he.hits = t.op_count[op];
        he.bytes = t.op_bytes[op];
        entries.push_back(std::move(he));
      }
      return PackHeatTrailer(entries);
    });
    // Hot-replication fan-out worker: beat responses electing this node
    // for replicate/drop work feed its queue; it pushes copies over the
    // sync-create path, byte-verifies them, and acks the tracker
    // (which is what publishes the widened replica set).
    HotReplCallbacks hcbs;
    hcbs.open_content = open_content_fn;
    hcbs.events = events_.get();
    hotrepl_ = std::make_unique<HotReplManager>(cfg_, std::move(hcbs));
    reporter_->set_hot_tasks_fn([this](const std::string& tracker_addr,
                                       const std::vector<HotTask>& tasks) {
      if (hotrepl_ != nullptr) hotrepl_->Enqueue(tracker_addr, tasks);
    });
    // Disk recovery (storage_disk_recovery.c): a wiped store path on a
    // server with prior sync state rebuilds itself from a group peer in
    // the background.  Decided BEFORE the first JOIN so the recovering
    // flag rides it — the node must never pass through ACTIVE (and take
    // reads for files it no longer has) on its way into recovery.
    recovery_ = std::make_unique<RecoveryManager>(cfg_, reporter_.get(),
                                                  &store_);
    // Each recovered file becomes its own trace (recovery.file root +
    // per-fetch child spans), with the context propagated onto the peer
    // so its FETCH_RECIPE/FETCH_CHUNK spans stitch cross-node.
    recovery_->SetTrace(trace_.get());
    // Recovered files dedup exactly like synced/uploaded ones: a rebuilt
    // node must not silently lose chunk-level dedup (its chunk store
    // would stay empty while peers dedup).  The hook runs on the
    // recovery thread, so it gets its own plugin instance where the
    // plugin wants one per thread (ChunkStore is internally locked).
    if (dedup_ != nullptr && !chunk_stores_.empty()) {
      recovery_dedup_ = dedup_->ForAnotherThread();
      DedupPlugin* rec_plugin =
          recovery_dedup_ != nullptr ? recovery_dedup_.get() : dedup_.get();
      recovery_->SetChunkedStore(
          [this, rec_plugin](const std::string& tmp, int spi, int64_t size,
                             const std::string& remote) {
            auto local = LocalPath(store_.store_path(spi), remote);
            if (!local.has_value()) return false;
            int64_t saved = 0, hits = 0;
            return StoreChunked(IngestFor(spi, rec_plugin, nullptr), tmp,
                                size, *local + ".rcp",
                                cfg_.group_name + "/" + remote, &saved, &hits);
          },
          cfg_.dedup_chunk_threshold);
      // Chunk-aware rebuild: pull the peer's recipe and only the chunk
      // bytes this node's store lacks (batched, ~8 MB per round-trip —
      // a per-chunk RPC would make low-dup rebuilds RTT-bound);
      // all-or-nothing with ref rollback, falling back to the full
      // download on any failure.
      recovery_->SetRecipeRecover(
          [this, rec_plugin](
              int spi, const std::string& remote, const Recipe& r,
              const RecoveryManager::FetchChunksFn& fetch_chunks,
              int64_t* chunks_fetched, int64_t* chunks_local) {
            if (spi >= static_cast<int>(chunk_stores_.size())) return false;
            ChunkStore* cs = chunk_stores_[spi].get();
            auto local = LocalPath(store_.store_path(spi), remote);
            if (!local.has_value()) return false;
            // Resumed recovery: both write paths are atomic (rename /
            // append-then-publish), so an existing file/recipe is
            // complete — re-storing would only inflate chunk refs.
            struct stat st;
            if (stat(local->c_str(), &st) == 0 ||
                cs->HasRecipe(*local + ".rcp"))
              return true;
            Recipe done;  // every ref taken so far (rollback set)
            done.logical_size = r.logical_size;
            auto fail = [&]() {
              cs->UnrefAll(done);
              return false;
            };
            // Pass 1: reference what this node already holds.
            std::vector<RecipeEntry> missing;
            for (const RecipeEntry& e : r.chunks) {
              if (cs->RefOne(e.digest_hex))
                done.chunks.push_back(e);
              else
                missing.push_back(e);
            }
            // Honest wire accounting: only the
            // misses cross the network; locally-ref'd chunks are the
            // savings the chunk-aware path exists for.
            *chunks_local = static_cast<int64_t>(done.chunks.size());
            *chunks_fetched = static_cast<int64_t>(missing.size());
            // Pass 2: fetch the misses in bounded batches.
            std::string payloads, err;
            size_t i = 0;
            while (i < missing.size()) {
              std::vector<RecipeEntry> want;
              int64_t batch_bytes = 0;
              while (i < missing.size() && batch_bytes < (8 << 20)) {
                want.push_back(missing[i]);
                batch_bytes += missing[i].length;
                ++i;
              }
              if (!fetch_chunks(want, &payloads)) return fail();
              size_t off = 0;
              for (const RecipeEntry& e : want) {
                const Admission a =
                    AdmitChunk(cs, e, payloads.data() + off, &done, &err);
                if (a == Admission::kNotItsDigest)
                  FDFS_LOG_WARN("recovery: chunk %s failed digest check",
                                e.digest_hex.c_str());
                if (a != Admission::kAdmitted) return fail();
                off += static_cast<size_t>(e.length);
              }
            }
            if (!cs->StoreRecipe(*local + ".rcp", r, &err)) return fail();
            // Re-register the file with a plugin that indexes beside the
            // chunk store (near-dup signature + attributions) exactly as
            // an upload would — zero extra wire, the bytes are local now.
            if (rec_plugin->IndexesBesideChunkStore())
              ReindexFromRecipe(IngestFor(spi, rec_plugin, nullptr), r,
                                cfg_.group_name + "/" + remote);
            return true;
          });
    }
    bool needs_recovery = recovery_->NeedsRecovery(store_.any_path_was_fresh());
    reporter_->set_recovering(needs_recovery);
    hotrepl_->Start();
    reporter_->Start();
    if (needs_recovery) recovery_->Start();
  }

  // Integrity engine: one background scrubber over every chunk store
  // (verify -> quarantine -> replica repair -> zero-ref GC).  Created
  // whenever chunk stores exist — with scrub_interval_s = 0 it only
  // runs when SCRUB_KICK forces a pass, so operators and tests can
  // drive deterministic passes on an otherwise-idle daemon.
  if (!chunk_stores_.empty()) {
    scrub_dedup_ = dedup_->ForAnotherThread();
    ScrubOptions sopts;
    sopts.interval_s = cfg_.scrub_interval_s;
    sopts.bandwidth_bytes_s =
        static_cast<int64_t>(cfg_.scrub_bandwidth_mb_s) << 20;
    sopts.ec_k = cfg_.ec_k;
    sopts.ec_m = cfg_.ec_m;
    sopts.ec_demote_age_s = cfg_.ec_demote_age_s;
    sopts.ec_bandwidth_bytes_s =
        static_cast<int64_t>(cfg_.ec_bandwidth_mb_s) << 20;
    // Demote ownership (jump hash) hashes over peers + self; this MUST
    // be the same "ip:port" the peers' sync lists carry for this node.
    sopts.self_id = MyIp() + ":" + std::to_string(cfg_.port);
    std::vector<ChunkStore*> stores;
    for (auto& cs : chunk_stores_) stores.push_back(cs.get());
    scrub_ = std::make_unique<ScrubManager>(
        sopts, cfg_.group_name, std::move(stores),
        [this]() {
          // Replica addresses for FETCH_CHUNK repair: the sync peer
          // list (every group member holds every chunk by design).
          std::vector<std::string> out;
          if (sync_ != nullptr)
            for (const SyncPeerState& s : sync_->States())
              out.push_back(s.addr);
          return out;
        },
        scrub_dedup_.get(), trace_.get(), events_.get());
    scrub_->Start();
  }

  // Rebalance migrator (ISSUE 11): idle until the tracker marks this
  // group DRAINING in the beat trailer, then migrates the files this
  // member was binlog source for into their jump-hash target groups.
  // Needs the reporter (drain signal + trackers) — standalone daemons
  // have nowhere to drain to.
  if (reporter_ != nullptr) {
    RebalanceOptions ropts;
    ropts.group_name = cfg_.group_name;
    ropts.base_path = cfg_.base_path;
    ropts.sync_dir = cfg_.base_path + "/data/sync";
    ropts.port = cfg_.port;
    ropts.trackers = cfg_.tracker_servers;
    rebalance_ = std::make_unique<RebalanceManager>(ropts, reporter_.get(),
                                                    events_.get());
    rebalance_->Start();
  }

  // Periodic maintenance (reference: sched_thread entries — binlog flush,
  // stat write, dedup snapshot).
  // Per-request access log (storage.conf:use_access_log).
  if (cfg_.use_access_log) {
    std::string path = cfg_.base_path + "/logs/access.log";
    access_log_ = fopen(path.c_str(), "a");
    if (access_log_ == nullptr)
      FDFS_LOG_WARN("cannot open access log %s", path.c_str());
  }
  // Restart-safe op counters (storage_write_to_stat_file analogue).
  stat_path_ = cfg_.base_path + "/data/storage_stat.dat";
  stats_.LoadFromFile(stat_path_);

  loop_.AddTimer(1000, [this]() { binlog_.Flush(); });
  loop_.AddTimer(1000, [this]() { RefreshClusterParams(); });
  loop_.AddTimer(10 * 1000, [this]() {
    stats_.SaveToFile(stat_path_);
    if (access_log_ != nullptr) fflush(access_log_);
  });
  loop_.AddTimer(60 * 1000, [this]() {
    if (dedup_ != nullptr) dedup_->Save();
  });
  // Negotiated-upload session sweep: a client that sent UPLOAD_RECIPE
  // and vanished must not pin chunks forever (a pinned chunk defers its
  // unlink on delete).  2s granularity against an upload_session_timeout
  // measured in tens of seconds is plenty.
  loop_.AddTimer(2000, [this]() { SweepIngestSessions(); });
  // Metrics tick: journal one registry snapshot and evaluate the SLO
  // rule table against the previous tick (both conf-gated above).
  if (cfg_.slo_eval_interval_s > 0 && (metrics_ != nullptr || slo_ != nullptr))
    loop_.AddTimer(cfg_.slo_eval_interval_s * 1000,
                   [this]() { MetricsTick(); });
  // Trunk maintenance (reference: trunk_create_file_advance + the
  // free-block checker driving compaction): keep one trunk file's worth
  // of pre-created free space ahead of demand and reclaim fully-free
  // files beyond the reserve.  Trunk-server role only.
  loop_.AddTimer(30 * 1000, [this]() {
    std::shared_ptr<TrunkAllocator> alloc;
    int64_t tfs;
    {
      std::lock_guard<RankedMutex> lk(trunk_mu_);
      if (!is_trunk_server_) return;
      alloc = trunk_alloc_;
      tfs = trunk_file_size_;
    }
    if (alloc == nullptr) return;
    alloc->EnsureFreeReserve(tfs);
    alloc->ReclaimEmptyFiles(/*keep=*/1);
  });

  // Active health probes: a dedicated thread so a stalled disk or
  // unreachable peer can never block the request path or the timers.
  probe_slow_noted_.assign(static_cast<size_t>(store_.store_path_count()),
                           false);
  if (cfg_.health_probe_interval_s > 0)
    health_probe_thread_ = std::thread([this] { HealthProbeMain(); });
  // DEBUG stall injection (watchdog_inject_stall_ms): a registered
  // thread that beats once, then sleeps past the watchdog threshold
  // without beating, then beats again — a deterministic stall+recovery
  // cycle for the watchdog tests.  Never enable in production.
  if (cfg_.watchdog_inject_stall_ms > 0) {
    inject_stall_thread_ = std::thread([this] {
      ScopedThreadName ledger("debug.stall");
      int64_t inject_us = static_cast<int64_t>(cfg_.watchdog_inject_stall_ms) *
                          1000;
      while (!health_stop_.load(std::memory_order_relaxed)) {
        BeatThreadHeartbeat();
        // The "stall": sit without beating for inject_ms, in small
        // sleeps so Stop() stays bounded.
        for (int64_t slept = 0;
             slept < inject_us && !health_stop_.load(std::memory_order_relaxed);
             slept += 50000)
          usleep(50000);
      }
    });
  }

  FDFS_LOG_INFO(
      "storage daemon up: group=%s port=%d store_paths=%d dedup=%s crc32=%s",
      cfg_.group_name.c_str(), cfg_.port, store_.store_path_count(),
      dedup_ != nullptr ? dedup_->Name() : "none",
      Crc32Chosen() == Crc32Impl::kFolded ? "folded" : "sliced");
  return true;
}

void StorageServer::Run() {
  // nio work threads (reference: storage_nio.c one-epoll-per-thread).
  // Started here — after Init and any daemonize fork — and joined in
  // Stop(); the main loop keeps accept + timers.  Every loop thread
  // joins the CPU ledger under its stable name (threadreg.h).
  for (size_t i = 0; i < nio_.size(); ++i) {
    EventLoop* lp = nio_[i]->loop.get();
    nio_[i]->thread = std::thread([lp, i] {
      ScopedThreadName ledger("nio.loop/" + std::to_string(i));
      lp->Run();
    });
  }
  ScopedThreadName ledger("main.loop");
  loop_.Run();
}

void StorageServer::Stop() {
  // Persist first: joining reporter threads can take up to one bounded
  // tracker-RPC timeout, and durability must not ride on that.
  if (dedup_ != nullptr) dedup_->Save();
  if (!stat_path_.empty()) stats_.SaveToFile(stat_path_);
  if (access_log_ != nullptr) {
    fclose(access_log_);
    access_log_ = nullptr;
  }
  binlog_.Flush();
  // Health threads check their stop flag inside short sleep slices
  // (and the prober between probes), so these joins are bounded even
  // mid-probe against a slow disk.
  health_stop_.store(true, std::memory_order_relaxed);
  if (health_probe_thread_.joinable()) health_probe_thread_.join();
  if (inject_stall_thread_.joinable()) inject_stall_thread_.join();
  // The scrubber may be mid-pass against the chunk stores; it checks
  // its stop flag between batches, so this join is bounded.
  if (scrub_ != nullptr) scrub_->Stop();
  // The migrator checks its stop flag between files (and inside its
  // pacing sleeps), so this join is bounded too.
  if (rebalance_ != nullptr) rebalance_->Stop();
  if (recovery_ != nullptr) recovery_->Stop();
  if (sync_ != nullptr) sync_->Stop();  // persists .mark cursors
  // The fan-out worker checks its stop flag between jobs and inside
  // its socket timeouts, so this join is bounded.
  if (hotrepl_ != nullptr) hotrepl_->Stop();
  if (reporter_ != nullptr) reporter_->Stop();
  // Order matters: dio pools drain first (their completions post to the
  // nio loops, which must still be running), then the nio loops stop and
  // drain their queues, then the main loop exits.
  for (auto& pool : dio_pools_) pool->Stop();
  for (auto& t : nio_) {
    t->loop->Stop();
    if (t->thread.joinable()) t->thread.join();
  }
  loop_.Stop();
}

bool StorageServer::DrainingRefusal() const {
  return reporter_ != nullptr && reporter_->group_state() != 0;
}

std::string StorageServer::MyIp() const {
  if (reporter_ != nullptr) return reporter_->my_ip();
  if (!cfg_.bind_addr.empty() && cfg_.bind_addr != "0.0.0.0")
    return cfg_.bind_addr;
  // Acquire pairs with AdmitConn's release-publish: state 2 means
  // my_ip_ is immutable from here on (any accept thread may have been
  // the writer under sharded accept).
  if (my_ip_state_.load(std::memory_order_acquire) != 2) return "127.0.0.1";
  return my_ip_.empty() ? "127.0.0.1" : my_ip_;
}

void StorageServer::DumpState() {
  FDFS_LOG_INFO(
      "state dump: conns=%lld refused=%lld upload=%lld/%lld "
      "download=%lld/%lld delete=%lld/%lld dedup_hits=%lld saved=%lldB "
      "binlog=%d",
      static_cast<long long>(conn_count_.load()),
      static_cast<long long>(refused_conn_count_.load()),
      static_cast<long long>(stats_.success_upload),
      static_cast<long long>(stats_.total_upload),
      static_cast<long long>(stats_.success_download),
      static_cast<long long>(stats_.total_download),
      static_cast<long long>(stats_.success_delete),
      static_cast<long long>(stats_.total_delete),
      static_cast<long long>(stats_.dedup_hits),
      static_cast<long long>(stats_.dedup_bytes_saved), binlog_.file_index());
  // Flight-recorder dump for postmortems: SIGUSR1 lands the retained
  // event ring in the daemon log as one JSON line (the same contract
  // the EVENT_DUMP opcode serves; OPERATIONS.md "Saturation & flight
  // recorder").
  if (events_ != nullptr)
    FDFS_LOG_INFO("event dump: %s",
                  events_->Json("storage", cfg_.port).c_str());
  // Thread ledger with heartbeat ages: which registered thread last
  // proved liveness and how long ago — "never" marks request-scoped
  // threads that don't beat (tools, short-lived workers).  The SIGUSR1
  // face of the watchdog (OPERATIONS.md "Health, probes & gray
  // failure").
  std::string ledger;
  for (const ThreadRegistry::HeartbeatEntry& hb :
       ThreadRegistry::Global().Heartbeats()) {
    if (!ledger.empty()) ledger += " ";
    ledger += hb.name + "(" + std::to_string(hb.tid) + ")=";
    ledger += hb.age_us < 0 ? std::string("never")
                            : std::to_string(hb.age_us / 1000) + "ms";
  }
  FDFS_LOG_INFO("thread ledger: %s", ledger.c_str());
}

// -- stats registry -------------------------------------------------------

namespace {

// Opcodes this daemon serves, with their monitor-facing names.  Sidecar
// RPC opcodes (DEDUP_*) are absent: the dedup engine answers those, not
// this server.
struct ServedOp {
  StorageCmd cmd;
  const char* name;
};
constexpr ServedOp kServedOps[] = {
    {StorageCmd::kUploadFile, "upload_file"},
    {StorageCmd::kUploadAppenderFile, "upload_appender_file"},
    {StorageCmd::kUploadSlaveFile, "upload_slave_file"},
    {StorageCmd::kDownloadFile, "download_file"},
    {StorageCmd::kDeleteFile, "delete_file"},
    {StorageCmd::kSetMetadata, "set_metadata"},
    {StorageCmd::kGetMetadata, "get_metadata"},
    {StorageCmd::kQueryFileInfo, "query_file_info"},
    {StorageCmd::kAppendFile, "append_file"},
    {StorageCmd::kModifyFile, "modify_file"},
    {StorageCmd::kTruncateFile, "truncate_file"},
    {StorageCmd::kCreateLink, "create_link"},
    {StorageCmd::kNearDups, "near_dups"},
    {StorageCmd::kActiveTest, "active_test"},
    {StorageCmd::kStat, "stat"},
    {StorageCmd::kSyncCreateFile, "sync_create_file"},
    {StorageCmd::kSyncDeleteFile, "sync_delete_file"},
    {StorageCmd::kSyncUpdateFile, "sync_update_file"},
    {StorageCmd::kSyncCreateLink, "sync_create_link"},
    {StorageCmd::kSyncAppendFile, "sync_append_file"},
    {StorageCmd::kSyncModifyFile, "sync_modify_file"},
    {StorageCmd::kSyncTruncateFile, "sync_truncate_file"},
    {StorageCmd::kSyncQueryChunks, "sync_query_chunks"},
    {StorageCmd::kSyncCreateRecipe, "sync_create_recipe"},
    {StorageCmd::kUploadRecipe, "upload_recipe"},
    {StorageCmd::kUploadChunks, "upload_chunks"},
    {StorageCmd::kQueryChunking, "query_chunking"},
    {StorageCmd::kFetchRecipe, "fetch_recipe"},
    {StorageCmd::kFetchChunk, "fetch_chunk"},
    {StorageCmd::kTraceDump, "trace_dump"},
    {StorageCmd::kEventDump, "event_dump"},
    {StorageCmd::kMetricsHistory, "metrics_history"},
    {StorageCmd::kHeatTop, "heat_top"},
    {StorageCmd::kScrubStatus, "scrub_status"},
    {StorageCmd::kScrubKick, "scrub_kick"},
    {StorageCmd::kEcStatus, "ec_status"},
    {StorageCmd::kEcKick, "ec_kick"},
    {StorageCmd::kEcRelease, "ec_release"},
    {StorageCmd::kFetchOnePathBinlog, "fetch_one_path_binlog"},
    {StorageCmd::kTrunkAllocSpace, "trunk_alloc_space"},
    {StorageCmd::kTrunkAllocConfirm, "trunk_alloc_confirm"},
    {StorageCmd::kTrunkFreeSpace, "trunk_free_space"},
    {StorageCmd::kProfileCtl, "profile_ctl"},
    {StorageCmd::kProfileDump, "profile_dump"},
    {StorageCmd::kHealthStatus, "health_status"},
};

}  // namespace

void StorageServer::InitStatsRegistry() {
  for (const ServedOp& op : kServedOps) {
    std::string base = std::string("op.") + op.name;
    OpStats& os = op_stats_[static_cast<uint8_t>(op.cmd)];
    os.count = registry_.Counter(base + ".count");
    os.errors = registry_.Counter(base + ".errors");
    os.latency_us = registry_.Histogram(base + ".latency_us",
                                        StatsRegistry::LatencyBucketsUs());
    op_names_[static_cast<uint8_t>(op.cmd)] = op.name;
  }
  // Saturation telemetry (ISSUE 6).  nio.loop_lag_us: per-iteration
  // callback time of every nio event loop — the p99 here is how long a
  // ready connection can wait behind other handlers, the queueing
  // signal the multi-reactor refactor (ROADMAP item 5) will be judged
  // against.  dio.queue_wait_us / dio.service_us: time disk work sat
  // queued behind other disk work vs time actually serviced, across
  // every store path's pool.
  hist_nio_lag_ = registry_.Histogram("nio.loop_lag_us",
                                      StatsRegistry::LatencyBucketsUs());
  ctr_nio_dispatched_ = registry_.Counter("nio.dispatched_ops");
  registry_.GaugeFn("nio.conns_active", [this] { return conn_count_.load(); });
  // Per-reactor accept spread (ISSUE 18): fed by both accept modes, so
  // a skewed nio.accepts.<i> distribution under reuseport is the kernel
  // hashing poorly, and under fallback it's the round-robin cursor.
  registry_.GaugeFn("nio.reuseport_active",
                    [this] { return reuseport_active_ ? 1 : 0; });
  for (size_t i = 0; i < nio_.size(); ++i) {
    NioThread* t = nio_[i].get();
    registry_.GaugeFn("nio.accepts." + std::to_string(i),
                      [t] { return t->accepts.load(); });
    registry_.GaugeFn("nio.conns." + std::to_string(i),
                      [t] { return t->live_conns.load(); });
  }
  hist_dio_wait_ = registry_.Histogram("dio.queue_wait_us",
                                       StatsRegistry::LatencyBucketsUs());
  hist_dio_service_ = registry_.Histogram("dio.service_us",
                                          StatsRegistry::LatencyBucketsUs());
  registry_.GaugeFn("dio.queue_depth", [this] {
    int64_t n = 0;
    for (const auto& p : dio_pools_) n += static_cast<int64_t>(p->pending());
    return n;
  });
  // The node's total over all store paths: what disk_writer_threads = 0
  // derived at start, or the operator's pin times the paths.
  registry_.GaugeFn("dio.workers", [this] {
    return static_cast<int64_t>(dio_workers_per_path_) *
           static_cast<int64_t>(dio_pools_.size());
  });
  // Flight-recorder health: throughput and ring-overwrite pressure.
  registry_.GaugeFn("events.recorded", [this] {
    return events_ != nullptr ? events_->recorded() : int64_t{0};
  });
  registry_.GaugeFn("events.dropped", [this] {
    return events_ != nullptr ? events_->dropped() : int64_t{0};
  });
  // Sampling profiler health (profiler.h): capture counters while a
  // window is armed, drop pressure when the slab overflows, and the
  // armed flag operators alert on (a profiler left running is overhead).
  registry_.GaugeFn("profile.samples",
                    [] { return Profiler::Global().samples(); });
  registry_.GaugeFn("profile.dropped",
                    [] { return Profiler::Global().dropped(); });
  registry_.GaugeFn("profile.active", [] {
    return static_cast<int64_t>(Profiler::Global().active() ? 1 : 0);
  });
  // SLO engine: how many rules are red right now (the one-read health
  // check fdfs_top's ALERTS line and scrapers key off).
  registry_.GaugeFn("slo.breaches_active", [this] {
    return slo_ != nullptr ? slo_->breaches_active() : int64_t{0};
  });
  registry_.GaugeFn("slo.breach_transitions", [this] {
    return slo_ != nullptr ? slo_->breach_transitions() : int64_t{0};
  });
  // Admission control & request QoS (ISSUE 19): ladder position, the
  // pressure score feeding it (milli-units — gauge-fns are int64), and
  // the admit/shed ledgers.  All atomic reads (the gauge-fn contract).
  registry_.GaugeFn("admission.level", [this] {
    return static_cast<int64_t>(admission_ != nullptr ? admission_->level()
                                                      : 0);
  });
  registry_.GaugeFn("admission.pressure_milli", [this] {
    return admission_ != nullptr ? admission_->pressure_milli() : int64_t{0};
  });
  registry_.GaugeFn("admission.ewma_milli", [this] {
    return admission_ != nullptr ? admission_->ewma_milli() : int64_t{0};
  });
  registry_.GaugeFn("admission.tightens", [this] {
    return admission_ != nullptr ? admission_->tightens() : int64_t{0};
  });
  registry_.GaugeFn("admission.relaxes", [this] {
    return admission_ != nullptr ? admission_->relaxes() : int64_t{0};
  });
  registry_.GaugeFn("admission.admitted", [this] {
    return admission_ != nullptr ? admission_->admitted() : int64_t{0};
  });
  registry_.GaugeFn("admission.shed_total", [this] {
    return admission_ != nullptr ? admission_->shed_total() : int64_t{0};
  });
  registry_.GaugeFn("admission.retry_after_ms", [this] {
    return admission_ != nullptr ? admission_->retry_after_ms() : int64_t{0};
  });
  registry_.GaugeFn("admission.inflight_bytes", [this] {
    return inflight_bytes_.load(std::memory_order_relaxed);
  });
  for (int i = 0; i < kPriorityClassCount; ++i) {
    registry_.GaugeFn(
        std::string("admission.shed.") +
            PriorityClassName(static_cast<uint8_t>(i)),
        [this, i] {
          return admission_ != nullptr ? admission_->shed_by_class(i)
                                       : int64_t{0};
        });
  }
  // Metrics journal health: retained bytes vs the conf cap, and how
  // many ticks this process has persisted.
  registry_.GaugeFn("metrics.journal_bytes", [this] {
    return metrics_ != nullptr ? metrics_->bytes_retained() : int64_t{0};
  });
  registry_.GaugeFn("metrics.journal_records", [this] {
    return metrics_ != nullptr ? metrics_->appended() : int64_t{0};
  });
  // Heat sketch health: tracked keys and lifetime touches (the
  // touches/capacity ratio bounds the sketch's overcount error).
  registry_.GaugeFn("heat.tracked", [this] {
    return heat_ != nullptr ? heat_->tracked() : int64_t{0};
  });
  registry_.GaugeFn("heat.touches", [this] {
    return heat_ != nullptr ? heat_->touches() : int64_t{0};
  });
  registry_.GaugeFn("heat.evictions", [this] {
    return heat_ != nullptr ? heat_->evictions() : int64_t{0};
  });
  // Fullest store path in percent — the disk_fill_pct SLO rule's input.
  // The gauge-fn only reads the cache: gauge-fns run UNDER the registry
  // mutex (Json/Snapshot), and a statvfs against a stalled disk or hung
  // NFS mount can block for seconds — which would freeze every STAT,
  // journal tick, and the nio loop serving them, exactly the saturation
  // this layer exists to diagnose.  RefreshDiskUsedPct runs the real
  // syscalls at startup, each metrics tick, and each beat.
  RefreshDiskUsedPct();
  registry_.GaugeFn("store.disk_used_pct",
                    [this] { return disk_used_pct_.load(); });
  // Filesystem inodes in use — refreshed off the registry lock exactly
  // like disk_used_pct (gauge-fns must never statvfs a stalled mount
  // under the registry mutex).  The number the slab-packing layout
  // (ISSUE 9) exists to flatten on small-file corpora.
  registry_.GaugeFn("store.inodes_used",
                    [this] { return inodes_used_.load(); });
  // Tracing health: ring throughput/overwrite pressure and the slow gate.
  registry_.GaugeFn("trace.spans_recorded", [this] {
    return trace_ != nullptr ? trace_->recorded() : int64_t{0};
  });
  registry_.GaugeFn("trace.spans_dropped", [this] {
    return trace_ != nullptr ? trace_->dropped() : int64_t{0};
  });
  registry_.GaugeFn("trace.slow_requests",
                    [this] { return slow_request_count_.load(); });
  // Gray-failure health layer (ISSUE 17).  peer.rpc_us: outbound RPC
  // latency across every op class, fed by the health monitor's NetRpc
  // observer — the peer_rpc_p99_ms SLO rule's input.  The probe and
  // watchdog gauge-fns only read atomics the "health.probe" thread and
  // the metrics tick refresh (the store.disk_used_pct discipline: a
  // gauge-fn must never touch a disk or a lock that can stall).
  hist_peer_rpc_ = registry_.Histogram("peer.rpc_us",
                                       StatsRegistry::LatencyBucketsUs());
  registry_.GaugeFn("store.probe_read_us",
                    [this] { return probe_read_us_.load(); });
  registry_.GaugeFn("store.probe_write_us",
                    [this] { return probe_write_us_.load(); });
  registry_.GaugeFn("watchdog.stalled_threads",
                    [this] { return stalled_threads_.load(); });
  hist_upload_bytes_ = registry_.Histogram(
      "upload.size_bytes", StatsRegistry::SizeBucketsBytes());
  hist_download_bytes_ = registry_.Histogram(
      "download.size_bytes", StatsRegistry::SizeBucketsBytes());
  ctr_sync_bytes_saved_wire_ = registry_.Counter("sync.bytes_saved_wire");
  ctr_sync_digest_mismatch_ = registry_.Counter("sync.digest_mismatch");
  ctr_chunkfetch_batches_ = registry_.Counter("chunkfetch.batches");
  ctr_chunkfetch_chunks_ = registry_.Counter("chunkfetch.chunks");
  ctr_chunkfetch_bytes_ = registry_.Counter("chunkfetch.bytes");
  // What the upload path's passes over a body cost and skip: bytes the
  // receive stage SHA-1'd (uploads whose digest Judge reads: neither
  // appender nor chunk-eligible), flat fall-backs of chunk-eligible
  // uploads that took the digest from the tmp file instead, and the
  // loop Crc32 chose at start (0 sliced, 1 folded).
  ctr_recv_hashed_bytes_ = registry_.Counter("upload.recv_hashed_bytes");
  ctr_fallback_rehash_ = registry_.Counter("upload.fallback_rehash");
  registry_.GaugeFn("crc32.impl",
                    [] { return static_cast<int64_t>(Crc32Chosen()); });
  ingest_ctrs_.chunk_hits = registry_.Counter("dedup.chunk_hits");
  ingest_ctrs_.chunk_misses = registry_.Counter("dedup.chunk_misses");
  ingest_ctrs_.ec_write_stripes = registry_.Counter("ec.write_stripes");
  ingest_ctrs_.ec_write_bytes = registry_.Counter("ec.write_bytes");
  ingest_ctrs_.ec_encode_failures = registry_.Counter("ec.encode_failures");
  // Negotiated uploads on the ingest edge (UPLOAD_RECIPE/UPLOAD_CHUNKS):
  // bytes_saved_wire counts chunk bytes the client never shipped because
  // the bitmap reported them present — the client-facing twin of
  // sync.bytes_saved_wire.
  ctr_ingest_recipe_uploads_ = registry_.Counter("ingest.recipe_uploads");
  ctr_ingest_bytes_saved_wire_ =
      registry_.Counter("ingest.bytes_saved_wire");
  ctr_ingest_fallbacks_ = registry_.Counter("ingest.recipe_fallbacks");
  // Chunks of committed negotiated uploads by where their bytes came
  // from (the store / the wire), and the commit's stages per request.
  ctr_ingest_chunks_present_ = registry_.Counter("ingest.chunks_present");
  ctr_ingest_chunks_shipped_ = registry_.Counter("ingest.chunks_shipped");
  // How a commit read the chunks the store had: the preadv calls
  // ReadChunkSlices made for commits and the chunks those served (chunks
  // a batch is the reading; chunks that are not slab-resident take its
  // per-chunk fall-through and count in neither).
  ingest_ctrs_.commit_read_batches =
      registry_.Counter("ingest.commit_read_batches");
  ingest_ctrs_.commit_read_chunks =
      registry_.Counter("ingest.commit_read_chunks");
  hist_ingest_negotiate_ = registry_.Histogram(
      "ingest.negotiate_us", StatsRegistry::LatencyBucketsUs());
  hist_ingest_present_ = registry_.Histogram(
      "ingest.commit_present_us", StatsRegistry::LatencyBucketsUs());
  hist_ingest_verify_ = registry_.Histogram(
      "ingest.commit_verify_us", StatsRegistry::LatencyBucketsUs());
  hist_ingest_reindex_ = registry_.Histogram(
      "ingest.reindex_us", StatsRegistry::LatencyBucketsUs());
  registry_.GaugeFn("ingest.sessions_active", [this] {
    std::lock_guard<RankedMutex> lk(ingest_mu_);
    return static_cast<int64_t>(ingest_sessions_.size());
  });
  // Read path (PR 5): ranged-download traffic and the hot-chunk read
  // cache, summed over the per-store-path chunk stores.
  ctr_download_ranged_requests_ =
      registry_.Counter("download.ranged_requests");
  ctr_download_ranged_bytes_ = registry_.Counter("download.ranged_bytes");
  ctr_dio_preadv_batches_ = registry_.Counter("dio.preadv_batches");
  ctr_dio_preadv_spans_ = registry_.Counter("dio.preadv_spans");
  auto cache_sum = [this](int64_t (ChunkStore::*fn)() const) {
    int64_t n = 0;
    for (const auto& cs : chunk_stores_) n += (cs.get()->*fn)();
    return n;
  };
  registry_.GaugeFn("cache.hits",
                    [cache_sum] { return cache_sum(&ChunkStore::cache_hits); });
  registry_.GaugeFn("cache.misses", [cache_sum] {
    return cache_sum(&ChunkStore::cache_misses);
  });
  registry_.GaugeFn("cache.evictions", [cache_sum] {
    return cache_sum(&ChunkStore::cache_evictions);
  });
  registry_.GaugeFn("cache.invalidations", [cache_sum] {
    return cache_sum(&ChunkStore::cache_invalidations);
  });
  registry_.GaugeFn("cache.bytes", [cache_sum] {
    return cache_sum(&ChunkStore::cache_bytes);
  });
  registry_.GaugeFn("cache.chunks", [cache_sum] {
    return cache_sum(&ChunkStore::cache_chunks);
  });
  registry_.GaugeFn("cache.capacity_bytes", [cache_sum] {
    return cache_sum(&ChunkStore::cache_capacity_bytes);
  });
  // Slab packing (ISSUE 9): slot/byte live-vs-dead accounting plus the
  // compactor's lifetime work, summed over the per-store-path slab
  // stores (all zero when slab_*_threshold = 0).
  registry_.GaugeFn("slab.files", [cache_sum] {
    return cache_sum(&ChunkStore::slab_files);
  });
  registry_.GaugeFn("slab.slots_live", [cache_sum] {
    return cache_sum(&ChunkStore::slab_slots_live);
  });
  registry_.GaugeFn("slab.slots_dead", [cache_sum] {
    return cache_sum(&ChunkStore::slab_slots_dead);
  });
  registry_.GaugeFn("slab.bytes_live", [cache_sum] {
    return cache_sum(&ChunkStore::slab_bytes_live);
  });
  registry_.GaugeFn("slab.bytes_dead", [cache_sum] {
    return cache_sum(&ChunkStore::slab_bytes_dead);
  });
  registry_.GaugeFn("slab.compactions", [cache_sum] {
    return cache_sum(&ChunkStore::slab_compactions);
  });
  registry_.GaugeFn("slab.compacted_bytes", [cache_sum] {
    return cache_sum(&ChunkStore::slab_compacted_bytes);
  });

  // Snapshot-time mirrors of live state.  The restart-persisted op
  // totals keep their wire names (kBeatStatNames) under "store." so the
  // STAT JSON and the tracker's beat feed agree field-for-field.
  static_assert(kBeatStatCount == 33, "update FillBeatStats + gauges");
  for (int i = 0; i < StorageStats::kPersisted; ++i) {
    registry_.GaugeFn(std::string("store.") + kBeatStatNames[i], [this, i] {
      int64_t v[StorageStats::kPersisted] = {0};
      stats_.Snapshot(v);
      return v[i];
    });
  }
  registry_.GaugeFn("server.connections",
                    [this] { return conn_count_.load(); });
  registry_.GaugeFn("server.refused_connections",
                    [this] { return refused_conn_count_.load(); });
  registry_.GaugeFn("binlog.file_index", [this] {
    return static_cast<int64_t>(binlog_.file_index());
  });
  registry_.GaugeFn("sync.lag_s.max", [this] { return MaxSyncLagS(); });
  registry_.GaugeFn("recovery.running", [this] {
    return static_cast<int64_t>(recovery_ != nullptr && recovery_->running());
  });
  registry_.GaugeFn("recovery.chunks_fetched", [this] {
    return recovery_ != nullptr ? recovery_->chunks_pulled() : int64_t{0};
  });
  registry_.GaugeFn("recovery.chunks_local", [this] {
    return recovery_ != nullptr ? recovery_->chunks_local() : int64_t{0};
  });
  registry_.GaugeFn("recovery.files_recovered", [this] {
    return recovery_ != nullptr ? recovery_->files_recovered() : int64_t{0};
  });
  registry_.GaugeFn("recovery.files_skipped", [this] {
    return recovery_ != nullptr ? recovery_->files_skipped() : int64_t{0};
  });
  // Integrity engine: mirror the SCRUB_STATUS blob field-for-field so
  // fdfs_monitor --prometheus exports scrub health without a second
  // RPC.  Names follow the wire contract (kScrubStatNames) under the
  // scrub. prefix; all zero when scrubbing is off (no chunk store).
  for (int i = 0; i < kScrubStatCount; ++i) {
    registry_.GaugeFn(std::string("scrub.") + kScrubStatNames[i],
                      [this, i] {
                        return scrub_ != nullptr ? scrub_->StatValue(i)
                                                 : int64_t{0};
                      });
  }
  // Erasure-coded cold tier (ISSUE 16): mirror the EC_STATUS blob the
  // same way — kEcStatNames under the ec. prefix, all zero when the
  // tier is off (no stripes and no scrubber).
  for (int i = 0; i < kEcStatCount; ++i) {
    registry_.GaugeFn(std::string("ec.") + kEcStatNames[i], [this, i] {
      return scrub_ != nullptr ? scrub_->EcStatValue(i) : int64_t{0};
    });
  }
  // Rebalance migrator (ISSUE 11): same names as the beat slots so
  // fdfs_monitor/fdfs_top read drain progress from either feed.
  registry_.GaugeFn("rebalance.files_moved", [this] {
    return rebalance_ != nullptr ? rebalance_->files_moved() : int64_t{0};
  });
  registry_.GaugeFn("rebalance.bytes_moved", [this] {
    return rebalance_ != nullptr ? rebalance_->bytes_moved() : int64_t{0};
  });
  registry_.GaugeFn("rebalance.files_pending", [this] {
    return rebalance_ != nullptr ? rebalance_->files_pending() : int64_t{0};
  });
  registry_.GaugeFn("rebalance.errors", [this] {
    return rebalance_ != nullptr ? rebalance_->errors() : int64_t{0};
  });
  registry_.GaugeFn("rebalance.done", [this] {
    return rebalance_ != nullptr ? rebalance_->done() : int64_t{0};
  });
  // Hot-replication fan-out worker (ISSUE 20): elected-member progress
  // counters; all zero on nodes never elected (or trackerless runs).
  registry_.GaugeFn("hot.fanout_replicated", [this] {
    return hotrepl_ != nullptr ? hotrepl_->replicated_total() : int64_t{0};
  });
  registry_.GaugeFn("hot.fanout_dropped", [this] {
    return hotrepl_ != nullptr ? hotrepl_->dropped_total() : int64_t{0};
  });
  registry_.GaugeFn("hot.fanout_verify_failures", [this] {
    return hotrepl_ != nullptr ? hotrepl_->verify_failures() : int64_t{0};
  });
  registry_.GaugeFn("hot.fanout_failures", [this] {
    return hotrepl_ != nullptr ? hotrepl_->failures_total() : int64_t{0};
  });
  registry_.GaugeFn("hot.fanout_queue", [this] {
    return hotrepl_ != nullptr ? hotrepl_->queue_depth() : int64_t{0};
  });
}

int64_t StorageServer::MaxSyncLagS() const {
  if (sync_ == nullptr) return 0;
  int64_t now = time(nullptr);
  int64_t mx = 0;
  for (const SyncPeerState& s : sync_->States()) {
    if (s.synced_ts > 0 && now - s.synced_ts > mx) mx = now - s.synced_ts;
  }
  return mx;
}

void StorageServer::RefreshPeerGauges() {
  // Per-peer replication gauges have dynamic names (peers come and go),
  // so they are plain gauges refreshed at snapshot time — and RETIRED
  // when their peer leaves the group (ISSUE 6 registry hygiene: a
  // long-lived daemon in a churning group must not grow unbounded
  // metric cardinality; nothing caches pointers to these gauges, so
  // pruning by name is safe).
  if (sync_ == nullptr) return;
  int64_t now = time(nullptr);
  std::vector<std::string> live;
  for (const SyncPeerState& s : sync_->States()) {
    std::string base = "sync.peer." + s.addr;
    live.push_back(base + ".");
    registry_.SetGauge(base + ".connected", s.connected ? 1 : 0);
    registry_.SetGauge(
        base + ".lag_s",
        s.synced_ts > 0 && now > s.synced_ts ? now - s.synced_ts : 0);
    registry_.SetGauge(base + ".records_synced", s.records_synced);
    registry_.SetGauge(base + ".records_skipped", s.records_skipped);
  }
  registry_.PruneGauges("sync.peer.", live);
}

std::string StorageServer::BuildStatsJson() {
  RefreshPeerGauges();
  HealthMonitor::Global().PublishGauges(&registry_);
  return registry_.Json();
}

void StorageServer::RefreshDiskUsedPct() {
  int64_t worst = 0;
  int64_t inodes = 0;
  std::vector<unsigned long> seen_fsids;
  for (int i = 0; i < store_.store_path_count(); ++i) {
    struct statvfs vfs;
    if (statvfs(store_.store_path(i).c_str(), &vfs) != 0 ||
        vfs.f_blocks == 0)
      continue;
    int64_t pct = static_cast<int64_t>(
        100.0 * (1.0 - static_cast<double>(vfs.f_bavail) /
                           static_cast<double>(vfs.f_blocks)));
    if (pct > worst) worst = pct;
    // Inodes in use, deduped by filesystem id (two store paths on one
    // filesystem must not double-count): the store.inodes_used gauge,
    // which shows what slab packing saves.
    bool dup = false;
    for (unsigned long id : seen_fsids) dup = dup || id == vfs.f_fsid;
    if (!dup) {
      seen_fsids.push_back(vfs.f_fsid);
      if (vfs.f_files >= vfs.f_ffree)
        inodes += static_cast<int64_t>(vfs.f_files - vfs.f_ffree);
    }
  }
  disk_used_pct_.store(worst);
  inodes_used_.store(inodes);
}

// -- gray-failure health layer (ISSUE 17) ---------------------------------

void StorageServer::HealthProbeMain() {
  ScopedThreadName ledger("health.probe");
  // First round 2s after startup (daemon fully up, reporter joined),
  // then per the conf cadence.  Sleeps are 250ms slices so Stop() stays
  // bounded, and each slice beats the heartbeat — the prober must never
  // look stalled to the watchdog it feeds.
  int64_t next_due = MonoUs() + 2 * 1000000;
  while (!health_stop_.load(std::memory_order_relaxed)) {
    BeatThreadHeartbeat();
    if (MonoUs() < next_due) {
      usleep(250000);
      continue;
    }
    RunHealthProbes();
    next_due = MonoUs() +
               static_cast<int64_t>(cfg_.health_probe_interval_s) * 1000000;
  }
}

void StorageServer::RunHealthProbes() {
  // Disk probes: one 4 KB tmp-write+fsync and one read-back per store
  // path, timed wall-clock.  A probe CAN block for seconds on a gray
  // mount — that's the measurement — which is why it runs on this
  // dedicated thread and publishes through atomics (gauge-fns and the
  // request path never touch the disk for health).
  int64_t thr_us = static_cast<int64_t>(cfg_.probe_slow_threshold_ms) * 1000;
  // A FAILED probe (open/write/fsync/read error) reads as slower than
  // any threshold: the disk.gray event fires and the score drops, which
  // is exactly what a dead mount deserves.
  int64_t fail_us = thr_us > 0 ? 8 * thr_us : 10 * 1000000;
  int64_t worst_read = 0, worst_write = 0;
  for (int i = 0; i < store_.store_path_count(); ++i) {
    std::string path = store_.store_path(i) + "/data/.health_probe.tmp";
    char block[4096];
    memset(block, 0x5a, sizeof(block));
    int64_t t0 = MonoUs();
    int64_t write_us = fail_us, read_us = fail_us;
    int fd = open(path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (fd >= 0) {
      if (write(fd, block, sizeof(block)) ==
              static_cast<ssize_t>(sizeof(block)) &&
          fsync(fd) == 0)
        write_us = MonoUs() - t0;
      close(fd);
    }
    BeatThreadHeartbeat();
    t0 = MonoUs();
    fd = open(path.c_str(), O_RDONLY);
    if (fd >= 0) {
      if (read(fd, block, sizeof(block)) ==
          static_cast<ssize_t>(sizeof(block)))
        read_us = MonoUs() - t0;
      close(fd);
    }
    BeatThreadHeartbeat();
    worst_write = std::max(worst_write, write_us);
    worst_read = std::max(worst_read, read_us);
    // One disk.gray event per outage per path (not per probe round):
    // probe_slow_noted_ is probe-thread-only state.
    bool slow = thr_us > 0 && std::max(write_us, read_us) > thr_us;
    if (slow && !probe_slow_noted_[static_cast<size_t>(i)]) {
      probe_slow_noted_[static_cast<size_t>(i)] = true;
      FDFS_LOG_WARN("gray disk: %s probe write=%lldus read=%lldus (>%dms)",
                    store_.store_path(i).c_str(),
                    static_cast<long long>(write_us),
                    static_cast<long long>(read_us),
                    cfg_.probe_slow_threshold_ms);
      if (events_ != nullptr)
        events_->Record(EventSeverity::kWarn, "disk.gray",
                        store_.store_path(i),
                        "probe write=" + std::to_string(write_us / 1000) +
                            "ms read=" + std::to_string(read_us / 1000) +
                            "ms threshold=" +
                            std::to_string(cfg_.probe_slow_threshold_ms) +
                            "ms");
    } else if (!slow && probe_slow_noted_[static_cast<size_t>(i)]) {
      probe_slow_noted_[static_cast<size_t>(i)] = false;
      if (events_ != nullptr)
        events_->Record(EventSeverity::kInfo, "disk.recovered",
                        store_.store_path(i), "");
    }
  }
  probe_read_us_.store(worst_read);
  probe_write_us_.store(worst_write);
  HealthMonitor::Global().SetProbe(worst_read, worst_write,
                                   cfg_.probe_slow_threshold_ms);

  // Active peer probes: ACTIVE_TEST to every tracker + group sync peer,
  // so an otherwise-idle cluster still converges on peer health.  The
  // NetRpc observer records each round-trip; only CONNECT failures
  // (no fd, so the observer never sees them) are fed explicitly.
  std::vector<std::pair<std::string, int>> targets;
  for (const std::string& t : cfg_.tracker_servers) {
    size_t colon = t.rfind(':');
    if (colon == std::string::npos || colon == 0) continue;
    targets.emplace_back(t.substr(0, colon), atoi(t.c_str() + colon + 1));
  }
  if (sync_ != nullptr) {
    for (const SyncPeerState& s : sync_->States()) {
      size_t colon = s.addr.rfind(':');
      if (colon == std::string::npos || colon == 0) continue;
      targets.emplace_back(s.addr.substr(0, colon),
                           atoi(s.addr.c_str() + colon + 1));
    }
  }
  for (const auto& [host, port] : targets) {
    if (health_stop_.load(std::memory_order_relaxed)) return;
    BeatThreadHeartbeat();
    int64_t t0 = MonoUs();
    std::string err;
    int fd = TcpConnect(host, port, 2000, &err);
    if (fd < 0) {
      HealthMonitor::Global().Feed(host + ":" + std::to_string(port),
                                   "probe", false, MonoUs() - t0, 2000);
      continue;
    }
    std::string resp;
    uint8_t status = 0;
    NetRpc(fd, static_cast<uint8_t>(StorageCmd::kActiveTest), "", &resp,
           &status, 1024, 2000);
    close(fd);
  }
}

std::string StorageServer::HealthStatusJson() {
  return HealthMonitor::Global().Json("storage", cfg_.port);
}

void StorageServer::MetricsTick() {
  // One snapshot feeds both consumers: what the journal persists IS
  // what the SLO engine judged, so a post-mortem can re-derive every
  // breach from the retained history.
  RefreshDiskUsedPct();
  RefreshPeerGauges();
  int64_t now_mono = MonoUs();
  // Per-thread CPU ledger: one /proc pass per tick, published as
  // thread.<name>.* gauges so the journal snapshot below persists them.
  ThreadRegistry::Global().SampleInto(&registry_);
  // Watchdog scan (gray-failure layer): a registered daemon thread
  // whose heartbeat is older than the threshold is stalled — wedged on
  // a lock, a dead NFS mount, an unbounded syscall.  Each transition
  // records one flight-recorder event (newly stalled / recovered), and
  // the live count feeds the gauge + this node's gray score.
  if (cfg_.watchdog_stall_threshold_ms > 0) {
    ThreadRegistry::WatchdogResult wd = ThreadRegistry::Global().WatchdogScan(
        static_cast<int64_t>(cfg_.watchdog_stall_threshold_ms) * 1000);
    stalled_threads_.store(static_cast<int64_t>(wd.stalled.size()));
    HealthMonitor::Global().SetStalledThreads(
        static_cast<int>(wd.stalled.size()));
    if (events_ != nullptr) {
      for (const ThreadRegistry::Stall& s : wd.stalled) {
        if (!s.newly) continue;
        FDFS_LOG_WARN("watchdog: thread %s (tid %d) stalled %llds",
                      s.name.c_str(), s.tid,
                      static_cast<long long>(s.age_us / 1000000));
        events_->Record(EventSeverity::kWarn, "watchdog.stall", s.name,
                        "heartbeat " + std::to_string(s.age_us / 1000) +
                            "ms old (threshold " +
                            std::to_string(cfg_.watchdog_stall_threshold_ms) +
                            "ms)");
      }
      for (const std::string& name : wd.recovered)
        events_->Record(EventSeverity::kInfo, "watchdog.recovered", name, "");
    }
  }
  // Health gauges (health.score + peer.* families) refresh here so the
  // journal snapshot below persists them every tick.
  HealthMonitor::Global().PublishGauges(&registry_);
  // Per-loop duty cycle: busy-us delta over the tick's wall time.
  // Index 0 = the accept/timers loop, 1 + i = nio_[i].
  if (loop_busy_last_.size() == nio_.size() + 1) {
    int64_t dwall = now_mono - last_tick_mono_us_;
    bool have_base = last_tick_mono_us_ > 0 && dwall > 0;
    for (size_t i = 0; i < loop_busy_last_.size(); ++i) {
      int64_t busy = i == 0 ? main_loop_busy_us_.load(std::memory_order_relaxed)
                            : nio_[i - 1]->busy_us.load(std::memory_order_relaxed);
      if (have_base) {
        int64_t pct = (busy - loop_busy_last_[i]) * 100 / dwall;
        if (pct < 0) pct = 0;
        if (pct > 100) pct = 100;
        registry_.SetGauge(
            i == 0 ? "nio.loop_busy_pct.main"
                   : "nio.loop_busy_pct." + std::to_string(i - 1),
            pct);
      }
      loop_busy_last_[i] = busy;  // first tick seeds the delta base
    }
  }
  StatsSnapshot snap;
  registry_.Snapshot(&snap);
  if (metrics_ != nullptr) metrics_->Append(TraceWallUs(), snap);
  double dt_s = static_cast<double>(now_mono - last_tick_mono_us_) / 1e6;
  if (dt_s <= 0) dt_s = 1.0;
  if (slo_ != nullptr && have_tick_snap_) {
    slo_->Tick(last_tick_snap_, snap, dt_s);
  }
  // Admission ladder tick AFTER the SLO tick: breaches_active then
  // reflects THIS snapshot's verdicts, so the ladder reacts the same
  // tick a breach starts.  One rung at most per tick; tighten/relax
  // transitions land in the flight recorder (the sloeval discipline).
  if (admission_ != nullptr) {
    AdmissionSignals sig;
    sig.breaches_active = slo_ != nullptr ? slo_->breaches_active() : 0;
    for (const auto& p : dio_pools_)
      sig.queue_depth += static_cast<int64_t>(p->pending());
    sig.inflight_bytes = inflight_bytes_.load(std::memory_order_relaxed);
    double lag_ms = 0;
    if (have_tick_snap_ &&
        SloEvaluator::ComputeReading("loop_lag_p99_ms", last_tick_snap_,
                                     snap, dt_s, &lag_ms))
      sig.loop_lag_p99_ms = lag_ms;
    int moved = admission_->Tick(sig);
    if (moved != 0 && events_ != nullptr) {
      char detail[128];
      snprintf(detail, sizeof(detail), "level=%d ewma=%.6g pressure=%.6g",
               admission_->level(), admission_->ewma_milli() / 1000.0,
               admission_->pressure_milli() / 1000.0);
      events_->Record(moved > 0 ? EventSeverity::kWarn : EventSeverity::kInfo,
                      moved > 0 ? "admission.tighten" : "admission.relax",
                      admission_->level_name(), detail);
    }
  }
  last_tick_snap_ = std::move(snap);
  have_tick_snap_ = true;
  last_tick_mono_us_ = now_mono;
}

void StorageServer::FillBeatStats(int64_t* out) {
  // Beats run on the tracker-client thread: a safe place to refresh
  // the disk gauge so it stays fresh even with the metrics tick off.
  RefreshDiskUsedPct();
  for (int i = 0; i < kBeatStatCount; ++i) out[i] = 0;
  stats_.Snapshot(out);  // slots [0, kPersisted)
  out[19] = conn_count_.load();
  out[20] = refused_conn_count_.load();
  out[21] = MaxSyncLagS();
  out[22] = ctr_sync_bytes_saved_wire_ != nullptr
                ? ctr_sync_bytes_saved_wire_->load() : 0;
  out[23] = recovery_ != nullptr ? recovery_->chunks_pulled() : 0;
  out[24] = recovery_ != nullptr ? recovery_->chunks_local() : 0;
  out[25] = recovery_ != nullptr ? recovery_->files_recovered() : 0;
  out[26] = ctr_chunkfetch_batches_ != nullptr
                ? ctr_chunkfetch_batches_->load() : 0;
  out[27] = ingest_ctrs_.chunk_misses != nullptr
                ? ingest_ctrs_.chunk_misses->load() : 0;
  // Rebalance migrator progress (ISSUE 11): the tracker leader's
  // auto-retire decision reads slots 30 (pending) and 32 (done) from
  // every ACTIVE member of a draining group.
  out[28] = rebalance_ != nullptr ? rebalance_->files_moved() : 0;
  out[29] = rebalance_ != nullptr ? rebalance_->bytes_moved() : 0;
  out[30] = rebalance_ != nullptr ? rebalance_->files_pending() : 0;
  out[31] = rebalance_ != nullptr ? rebalance_->errors() : 0;
  out[32] = rebalance_ != nullptr ? rebalance_->done() : 0;
}

// -- nio ------------------------------------------------------------------

bool StorageServer::AdmitConn(int fd) {
  SetNonBlocking(fd);
  SetNoDelay(fd);  // responses are header-write + body-write pairs
  if (cfg_.max_connections > 0 &&
      conn_count_.load() >= cfg_.max_connections) {
    // Polite refusal (reference: fast_task_queue pool exhaustion):
    // one EBUSY response header, then close.  A fresh socket's send
    // buffer always takes 10 bytes, so a blocking write is safe.
    uint8_t hdr[kHeaderSize] = {0};
    hdr[8] = static_cast<uint8_t>(StorageCmd::kResp);
    hdr[9] = 16;  // EBUSY
    (void)!write(fd, hdr, sizeof(hdr));
    close(fd);
    refused_conn_count_++;
    return false;
  }
  // First-conn local-ip capture, lock-free: with sharded accept this
  // races across reactor threads, so one writer wins the 0->1 CAS and
  // release-publishes state 2; MyIp() acquires before reading.
  int st = 0;
  if (my_ip_state_.load(std::memory_order_relaxed) == 0 &&
      my_ip_state_.compare_exchange_strong(st, 1,
                                           std::memory_order_relaxed)) {
    my_ip_ = SockIp(fd);
    my_ip_state_.store(2, std::memory_order_release);
  }
  // Count at accept time, not adoption: a connect burst drains the
  // whole backlog here before any nio thread runs its posted
  // AdoptConn, so a later increment would let the burst sail past the
  // cap.  CloseConn owns the decrement.
  conn_count_++;
  return true;
}

void StorageServer::OnAccept(uint32_t) {
  for (;;) {
    int fd = accept(listen_fd_, nullptr, nullptr);
    if (fd < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) return;
      FDFS_LOG_WARN("accept: %s", strerror(errno));
      return;
    }
    if (!AdmitConn(fd)) continue;
    // Round-robin handoff to a nio work thread (reference:
    // storage_nio.c pipe-notify from the accept thread).
    NioThread* t = nio_[next_nio_++ % nio_.size()].get();
    t->accepts.fetch_add(1, std::memory_order_relaxed);
    t->loop->Post([this, t, fd] { AdoptConn(t, fd); });
  }
}

void StorageServer::OnReactorAccept(NioThread* t) {
  // Runs on t's own loop thread: the kernel spread the connection to
  // this reactor's SO_REUSEPORT listener, so adoption is inline — no
  // cross-loop Post, no shared next_nio_ cursor.
  for (;;) {
    int fd = accept(t->listen_fd, nullptr, nullptr);
    if (fd < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) return;
      FDFS_LOG_WARN("accept (reactor): %s", strerror(errno));
      return;
    }
    if (!AdmitConn(fd)) continue;
    t->accepts.fetch_add(1, std::memory_order_relaxed);
    AdoptConn(t, fd);
  }
}

void StorageServer::AdoptConn(NioThread* t, int fd) {
  auto conn = std::make_unique<Conn>();
  conn->fd = fd;
  conn->owner = t;
  Conn* raw = conn.get();
  t->conns[fd] = std::move(conn);  // conn_count_ was taken at accept
  t->live_conns.fetch_add(1, std::memory_order_relaxed);
  t->loop->Add(fd, EPOLLIN, [this, raw](uint32_t ev) { OnConnEvent(raw, ev); });
}

// Per-request latency stamps use common/net.h MonoUs() — the same
// clock WorkerPool and the loop-lag hook measure with, so queue-wait
// subtractions across producers can never mix clock sources.

void StorageServer::OffloadToDio(Conn* c, int spi, std::function<void()> work) {
  WorkerPool* pool = nullptr;
  if (!dio_pools_.empty()) {
    size_t i = (spi >= 0 && spi < static_cast<int>(dio_pools_.size()))
                   ? static_cast<size_t>(spi) : 0;
    pool = dio_pools_[i].get();
  }
  if (pool == nullptr) {  // degraded: run inline (still correct)
    StageTraceBinding stages(&c->stages);
    work();
    return;
  }
  c->async_pending = true;
  c->work_start_us = MonoUs();  // dio-stage begin (access log AND spans)
  EventLoop* loop = ConnLoop(c);
  // Drop the fd from epoll while a worker owns the request: with
  // level-triggered epoll a readable/HUP'd socket would otherwise
  // re-fire every wait and spin this nio thread for the whole job.
  loop->Del(c->fd);
  pool->Submit([this, c, loop, work = std::move(work)] {
    // Worker context: `work` may Respond()/RespondError() — both only
    // BUILD the response while async_pending is set; the socket and
    // epoll are touched exclusively from the loop thread below.
    // Queue-wait interval: time between submit (work_start_us) and this
    // pickup is saturation, not service — the dio.queue_wait stage (the
    // conn is worker-owned while async_pending, so writing its recorder
    // here is race-free).  Floor of 1µs: an idle pool can pick up within
    // the clock tick, and a 0 would suppress the span — the timeline
    // should always show the wait stage, even when it reads "~0".
    c->stages.Add(Stage::kDioWait, c->work_start_us,
                  std::max(MonoUs(), c->work_start_us + 1));
    {
      // dedup.cc and StoreChunked reach the recorder through this.
      StageTraceBinding stages(&c->stages);
      work();
    }
    loop->Post([this, c, loop] {
      c->async_pending = false;
      if (c->dead) {  // closed while the worker ran
        auto& z = c->owner->zombies;
        for (auto it = z.begin(); it != z.end(); ++it) {
          if (it->get() == c) {
            z.erase(it);
            break;
          }
        }
        return;
      }
      loop->Add(c->fd, EPOLLIN, [this, c](uint32_t ev) { OnConnEvent(c, ev); });
      if (c->state == ConnState::kSend)
        WriteConn(c);   // flush the prepared response
      else
        ReadConn(c);    // e.g. RespondError flipped to drain mode
    });
  });
}

void StorageServer::OnConnEvent(Conn* c, uint32_t events) {
  // While a dio worker owns the request, the loop must not touch the
  // conn — not even for HUP (the worker would race a CloseConn); a dead
  // peer is discovered when the response flush fails.
  if (c->async_pending) return;
  if (events & (EPOLLHUP | EPOLLERR)) {
    CloseConn(c);
    return;
  }
  if (events & EPOLLOUT) {
    if (!WriteConn(c)) return;
  }
  if (events & EPOLLIN) ReadConn(c);
}

void StorageServer::CloseConn(Conn* c) {
  // Identity check FIRST: a hypothetical double-CloseConn after the fd
  // was reused by a new conn must not close the stranger's fd or
  // double-decrement the counter.
  auto& conns = c->owner->conns;
  auto it = conns.find(c->fd);
  if (it == conns.end() || it->second.get() != c) return;
  AbortFileOp(c);  // disconnect mid-op: same rollback as an explicit error
  // Mid-request death: the admitted bytes never reached LogAccess —
  // release them here or the in-flight ledger leaks upward forever.
  if (c->inflight_acct != 0) {
    inflight_bytes_.fetch_sub(c->inflight_acct, std::memory_order_relaxed);
    c->inflight_acct = 0;
  }
  if (c->send_fd >= 0) close(c->send_fd);
  c->rstream.reset();
  int fd = c->fd;
  ConnLoop(c)->Del(fd);
  close(fd);
  conn_count_--;
  c->owner->live_conns.fetch_sub(1, std::memory_order_relaxed);
  if (c->async_pending) {
    // A dio worker still references this conn: keep the object alive as
    // a zombie until its completion callback reaps it.
    c->dead = true;
    c->fd = -1;
    c->owner->zombies.push_back(std::move(it->second));
  }
  conns.erase(it);
}

void StorageServer::ResetForNextRequest(Conn* c) {
  ReleaseBusy(c);  // normally already released; guards every exit path
  c->state = ConnState::kRecvHeader;
  c->header_got = 0;
  c->fixed.clear();
  c->fixed_need = 0;
  c->pkg_len = 0;
  c->cmd = 0;
  c->body_consumed = 0;
  c->close_after_send = false;
  c->file_fd = -1;
  c->tmp_path.clear();
  c->file_remaining = 0;
  c->file_size = 0;
  c->ext.clear();
  c->hashing = false;
  c->replica_op = 0;
  c->sync_remote.clear();
  c->range_offset = 0;
  c->slave_prefix.clear();
  c->discarding = false;
  c->pending_status = 0;
  c->pending_body.clear();
  c->priority = kPriorityUntagged;
  c->resolved_priority = 0;
  c->out.clear();
  c->out_off = 0;
  c->send_fd = -1;
  c->send_off = 0;
  c->send_remaining = 0;
  c->rstream.reset();
  c->work_start_us = 0;
  c->ingest_session = 0;
  c->ingest_chunks_total = 0;
  c->ingest_chunks_missing = 0;
  c->trace_ctx = TraceCtx{};
  c->traced = false;
  c->trace_span = 0;
  // Bounded buffer budget (the other half of fast_task_queue's pooled
  // buffers): a request with an unusually large in-memory body or
  // response must not pin that capacity for the connection's lifetime —
  // max_connections × retained buffers is the daemon's memory bound.
  const size_t budget = static_cast<size_t>(cfg_.buff_size);
  if (c->fixed.capacity() > budget) std::string().swap(c->fixed);
  if (c->out.capacity() > budget) std::string().swap(c->out);
}

bool StorageServer::AcquireBusy(Conn* c, const std::string& remote) {
  std::lock_guard<RankedMutex> lk(busy_mu_);
  if (busy_files_.count(remote)) return false;
  busy_files_.insert(remote);
  c->busy_key = remote;
  return true;
}

void StorageServer::ReleaseBusy(Conn* c) {
  if (!c->busy_key.empty()) {
    std::lock_guard<RankedMutex> lk(busy_mu_);
    busy_files_.erase(c->busy_key);
    c->busy_key.clear();
  }
}

void StorageServer::AbortFileOp(Conn* c) {
  // Failure/abort cleanup for any in-flight file write.  In-place range
  // writes (append/modify, no tmp file) roll back appends by truncating to
  // the pre-op size so a retry or replica replay never sees partial bytes;
  // a partial modify rewrites existing content and has no undo, but the
  // binlog record is only emitted on success, so replicas stay on the old
  // content either way.
  if (c->file_fd >= 0) {
    if (c->tmp_path.empty()) {
      auto cmd = static_cast<StorageCmd>(c->cmd);
      if (cmd == StorageCmd::kAppendFile || cmd == StorageCmd::kSyncAppendFile)
        ftruncate(c->file_fd, c->range_offset);
    }
    close(c->file_fd);
    c->file_fd = -1;
    if (!c->tmp_path.empty()) {
      unlink(c->tmp_path.c_str());
      c->tmp_path.clear();
    }
  }
  ReleaseBusy(c);
}

void StorageServer::RespondError(Conn* c, uint8_t status) {
  // An early error can leave unread request bytes on the socket; a keepalive
  // reuse would parse them as the next header.  Drain and discard them, then
  // send the error — the connection stays usable (the reference's client
  // pool would otherwise have to reconnect after every rejected request).
  AbortFileOp(c);
  if (c->body_consumed >= c->pkg_len) {
    Respond(c, status);
    return;
  }
  c->discarding = true;
  c->pending_status = status;
  c->file_remaining = c->pkg_len - c->body_consumed;
  c->state = ConnState::kRecvFile;
}

void StorageServer::ShedRequest(Conn* c, int64_t retry_after_ms) {
  // Admission shed: EBUSY + an 8-byte BE retry-after-ms hint the client
  // honors with jittered backoff.  Same drain discipline as
  // RespondError (the connection stays usable — a shed must not force
  // a reconnect, which would ADD load under overload), but the hint
  // body has to survive the drain, hence pending_body.
  AbortFileOp(c);
  c->shed_resp = true;
  std::string hint(8, '\0');
  PutInt64BE(retry_after_ms, reinterpret_cast<uint8_t*>(hint.data()));
  if (c->body_consumed >= c->pkg_len) {
    Respond(c, 16 /*EBUSY*/, hint);
    return;
  }
  c->discarding = true;
  c->pending_status = 16;
  c->pending_body = std::move(hint);
  c->file_remaining = c->pkg_len - c->body_consumed;
  c->state = ConnState::kRecvFile;
}

void StorageServer::Respond(Conn* c, uint8_t status, const std::string& body) {
  LogAccess(c, status, static_cast<int64_t>(body.size()));
  c->out.resize(kHeaderSize);
  PutInt64BE(static_cast<int64_t>(body.size()),
             reinterpret_cast<uint8_t*>(c->out.data()));
  c->out[8] = static_cast<char>(StorageCmd::kResp);
  c->out[9] = static_cast<char>(status);
  c->out += body;
  c->out_off = 0;
  c->state = ConnState::kSend;
  // From a dio worker this only stages the response; the completion
  // callback flushes it on the loop thread.
  if (!c->async_pending) WriteConn(c);
}

void StorageServer::NoteHeat(Conn* c, HeatOp op, const std::string& key) {
  if (heat_ == nullptr) return;
  c->heat_key = key;
  c->heat_op = static_cast<uint8_t>(op);
}

void StorageServer::LogAccess(Conn* c, uint8_t status, int64_t bytes) {
  if (c->req_start_us == 0) return;  // one accounting pass per request
  // The request is answered: its bytes leave the admission in-flight
  // ledger (zeroing the field makes the subtract single-shot even if a
  // CloseConn follows).
  if (c->inflight_acct != 0) {
    inflight_bytes_.fetch_sub(c->inflight_acct, std::memory_order_relaxed);
    c->inflight_acct = 0;
  }
  int64_t now_us = MonoUs();
  // Heat telemetry: one Touch per request at the accounting choke point
  // (handlers that resolved a file-id stamped heat_key).  Uploads
  // attribute logical payload bytes; downloads/fetches the bytes served.
  if (heat_ != nullptr && !c->heat_key.empty()) {
    HeatOp hop = static_cast<HeatOp>(c->heat_op);
    int64_t hb = 0;
    if (status == 0)
      hb = hop == HeatOp::kUpload ? c->file_size : (bytes > 0 ? bytes : 0);
    heat_->Touch(c->heat_key, hop, hb, status != 0);
  }
  // Registry side (always on): per-opcode count/error/latency plus the
  // transfer-size histograms.  Handles are pre-registered atomics —
  // callable from nio loops and dio workers alike.
  // Shed requests stay out of the op stats entirely: the SLO engine
  // reads error_rate_pct / request_p99_ms off these counters, and a
  // ladder whose refusals raise the very breach that feeds its
  // pressure score would latch itself tight (the admission gauges
  // already count every shed).  The access log below still records
  // them for forensics.
  const OpStats& os = op_stats_[c->cmd];
  if (os.count != nullptr && !c->shed_resp) {
    os.count->fetch_add(1, std::memory_order_relaxed);
    if (status != 0) os.errors->fetch_add(1, std::memory_order_relaxed);
    os.latency_us->Observe(now_us - c->req_start_us);
  }
  switch (static_cast<StorageCmd>(c->cmd)) {
    case StorageCmd::kUploadFile:
    case StorageCmd::kUploadAppenderFile:
    case StorageCmd::kUploadSlaveFile:
      if (status == 0 && hist_upload_bytes_ != nullptr)
        hist_upload_bytes_->Observe(c->file_size);
      break;
    case StorageCmd::kUploadChunks:  // file_size = logical, not wire bytes
      if (status == 0 && hist_upload_bytes_ != nullptr) {
        hist_upload_bytes_->Observe(c->file_size);
        hist_ingest_present_->Observe(c->stages.Sum(Stage::kPresent));
        hist_ingest_verify_->Observe(c->stages.Sum(Stage::kVerify));
        hist_ingest_reindex_->Observe(c->stages.Sum(Stage::kReindex));
      }
      break;
    case StorageCmd::kUploadRecipe:
      if (status == 0 && hist_ingest_negotiate_ != nullptr)
        hist_ingest_negotiate_->Observe(c->stages.Sum(Stage::kNegotiate));
      break;
    case StorageCmd::kDownloadFile:
      if (status == 0 && hist_download_bytes_ != nullptr)
        hist_download_bytes_->Observe(bytes);
      break;
    default:
      break;
  }
  if (access_log_ != nullptr) {
    // "<epoch.sec> <client_ip> <cmd> <status> <bytes> <cost_us>
    //  <recv_us> <work_us> <fp_us> <fp_lock_us> <cswrite_us> <binlog_us>
    //  <req_bytes> <cdc_us> <dio_wait_us> <readback_us> <negotiate_us>
    //  <present_us> <verify_us> <recipe_us> <reindex_us> <ec_us>" —
    // per-stage split
    // (SURVEY.md §5).  work = dio-stage time; every other stage column
    // is the sum of the request's intervals of one stage (c->stages,
    // common/trace.h): recv = body receive window, then the
    // chunked-upload splits inside the work window (fingerprint wall, its
    // sidecar-lock-wait share, chunk-store writes, binlog append);
    // req_bytes = request body size (wire accounting — e.g. chunk-aware
    // replication's savings show up here); then, appended so that readers
    // by position keep working: cdc = the native chunker's share of fp,
    // dio_wait = the wait in the dio queue at the head of the work
    // window, readback = the tmp file read back segment by segment before
    // each fingerprint call (inside work, outside fp); and last the
    // negotiated upload's own stages: negotiate (UPLOAD_RECIPE: parse +
    // PinAndMask), then of an UPLOAD_CHUNKS commit, summed over its
    // segments: present (RefOne of the chunks the store had, their one
    // batched read into the segment buffer, the CRC over the assembled
    // segment), verify (digest check + PutAndRef of the shipped ones),
    // recipe (id + recipe write; cswrite holds these three) and reindex
    // (the assembled segment cut and fingerprinted for the file's
    // signature, the answer held against the client's recipe, the
    // fingerprint session committed; cdc and fp_lock are then shares of
    // it); and ec = erasure coding on write (IngestFor): each segment's
    // new chunks found, laid into a stripe, its parity asked of the
    // sidecar, its shards and manifest written, the references taken (a
    // share of cswrite).  Columns are 0 when a stage did not occur;
    // tools/access_log_stages.py aggregates them into the bench stage
    // table.
    //
    // After the row, the intervals themselves, for a request that
    // recorded any: one compact JSON line (StageLineJson; a single token,
    // which every column parser skips like the slow-request line).
    // Formatted only here, with the log on, and before the lock.
    const StageTrace& st = c->stages;
    const int64_t cost_us = now_us - c->req_start_us;
    std::string stage_line;
    if (st.n > 0)
      stage_line = StageLineJson(st, c->cmd, status, c->req_start_us,
                                 TraceWallUs() - cost_us, cost_us);
    int64_t work_us =
        c->work_start_us > 0 ? now_us - c->work_start_us : 0;
    std::lock_guard<RankedMutex> lk(log_mu_);
    fprintf(access_log_,
            "%lld %s %d %d %lld %lld %lld %lld %lld %lld %lld %lld %lld %lld "
            "%lld %lld %lld %lld %lld %lld %lld %lld\n",
            static_cast<long long>(time(nullptr)), c->peer_ip.c_str(), c->cmd,
            status, static_cast<long long>(bytes),
            static_cast<long long>(cost_us),
            static_cast<long long>(st.Sum(Stage::kRecv)),
            static_cast<long long>(work_us),
            static_cast<long long>(st.Sum(Stage::kFingerprint)),
            static_cast<long long>(st.Sum(Stage::kFpLock)),
            static_cast<long long>(st.Sum(Stage::kCsWrite)),
            static_cast<long long>(st.Sum(Stage::kBinlog)),
            static_cast<long long>(c->pkg_len),
            static_cast<long long>(st.Sum(Stage::kCdc)),
            static_cast<long long>(st.Sum(Stage::kDioWait)),
            static_cast<long long>(st.Sum(Stage::kReadback)),
            static_cast<long long>(st.Sum(Stage::kNegotiate)),
            static_cast<long long>(st.Sum(Stage::kPresent)),
            static_cast<long long>(st.Sum(Stage::kVerify)),
            static_cast<long long>(st.Sum(Stage::kRecipe)),
            static_cast<long long>(st.Sum(Stage::kReindex)),
            static_cast<long long>(st.Sum(Stage::kEcWrite)));
    if (!stage_line.empty()) {
      fputs(stage_line.c_str(), access_log_);
      fputc('\n', access_log_);
    }
  }
  // Spans AFTER the column line: the slow gate's immediate fflush then
  // pushes this request's own access-log record out with the JSON line
  // (a slow-flush that precedes the column write would publish a log in
  // which the slow request has no parseable column row — observed as a
  // fast-host race in the slow-gate integration test).
  RecordRequestSpans(c, status, now_us, bytes);
  // One line per request.  c->stages is left as it is until the next
  // request's header resets it: a stage guard may still close after the
  // response was built.
  c->req_start_us = 0;
  c->work_start_us = 0;
  c->heat_key.clear();
  c->heat_op = 0;
}

void StorageServer::RecordRequestSpans(Conn* c, uint8_t status,
                                       int64_t now_us, int64_t bytes) {
  if (trace_ == nullptr) return;
  int64_t total_us = now_us - c->req_start_us;
  int64_t slow_us = cfg_.slow_request_threshold_ms * 1000;
  bool slow = slow_us > 0 && total_us >= slow_us;
  if (!c->traced && !slow) return;

  // Spans are stamped on the wall clock (cross-node stitching needs one
  // clock domain); stage offsets come from the monotonic stamps the
  // access log already keeps, anchored to the request's wall start.
  int64_t wall_start = TraceWallUs() - total_us;
  TraceSpan root;
  root.trace_id = c->traced ? c->trace_ctx.trace_id : trace_->NewTraceId();
  root.span_id = c->trace_span != 0 ? c->trace_span : trace_->NextSpanId();
  root.parent_id = c->traced ? c->trace_ctx.parent_span : 0;
  root.start_us = wall_start;
  root.dur_us = total_us;
  root.status = status;
  root.flags =
      (c->traced ? c->trace_ctx.flags : 0) | (slow ? kTraceFlagSlow : 0);
  const char* opname =
      op_names_[c->cmd] != nullptr ? op_names_[c->cmd] : "unknown";
  char full[sizeof(root.name)];
  std::snprintf(full, sizeof(full), "storage.%s", opname);
  root.SetName(full);
  trace_->Record(root);

  // Returns the span's id (0 when the stage did not occur), so that a
  // stage inside another can name it as `parent`.
  auto child = [&](const char* name, int64_t start, int64_t dur,
                   uint32_t parent = 0) -> uint32_t {
    if (dur <= 0) return 0;
    TraceSpan s;
    s.trace_id = root.trace_id;
    s.span_id = trace_->NextSpanId();
    s.parent_id = parent != 0 ? parent : root.span_id;
    s.start_us = start;
    s.dur_us = dur;
    s.flags = root.flags;
    s.SetName(name);
    trace_->Record(s);
    return s.span_id;
  };
  // The request's stages as they happened (c->stages): each interval
  // under its real parent, its wall start the request's plus the
  // interval's monotonic offset.  dio.queue_wait is WAITING, not working
  // — the span that makes a saturated dio pool visible on an fdfs_trace
  // timeline.  How a commit's present chunks were read hangs under each
  // segment's present span as an annotation: chunks served by a preadv /
  // preadv calls.
  const StageTrace& st = c->stages;
  uint32_t ids[StageTrace::kCapacity];
  for (int i = 0; i < st.n; ++i) {
    const StageTrace::Interval& v = st.iv[i];
    const int64_t start = wall_start + (v.start_us - c->req_start_us);
    const int64_t dur = v.end_us - v.start_us;
    ids[i] = child(StageName(v.stage), start, dur,
                   v.parent >= 0 ? ids[v.parent] : 0);
    if (v.stage == Stage::kPresent && ids[i] != 0 && v.args[1] > 0) {
      char ann[sizeof(TraceSpan{}.name)];
      if (std::snprintf(ann, sizeof(ann), "ingest.commit_reads %lld/%lld",
                        static_cast<long long>(v.args[0]),
                        static_cast<long long>(v.args[1])) > 0)
        child(ann, start, dur, ids[i]);
    }
  }
  int64_t work_wall =
      wall_start + (c->work_start_us > 0 ? c->work_start_us - c->req_start_us
                                         : st.Sum(Stage::kRecv));
  if (c->ingest_chunks_total > 0) {
    // Negotiated-upload annotation: how much of the recipe actually
    // crossed the wire (missing/total), spanning the request's work
    // window so the timeline shows the split alongside the stages.
    char ann[sizeof(TraceSpan{}.name)];
    std::snprintf(ann, sizeof(ann), "ingest.chunks %lld/%lld",
                  static_cast<long long>(c->ingest_chunks_missing),
                  static_cast<long long>(c->ingest_chunks_total));
    child(ann, work_wall,
          c->work_start_us > 0 ? now_us - c->work_start_us : total_us);
  }

  if (slow) {
    slow_request_count_.fetch_add(1, std::memory_order_relaxed);
    if (events_ != nullptr)
      events_->Record(EventSeverity::kWarn, "request.slow", root.name,
                      "peer=" + c->peer_ip +
                          " dur_us=" + std::to_string(total_us) +
                          " status=" + std::to_string(status));
    std::string line =
        SlowRequestJson("storage", root.name, root, c->peer_ip, bytes);
    FDFS_LOG_WARN("%s", line.c_str());
    if (access_log_ != nullptr) {
      // One compact-JSON line amid the space-separated records: the
      // plain column parser skips it, access_log_stages --slow reads it.
      // Flushed immediately — slow requests are rare and the line is
      // an operator signal, not bulk logging.
      std::lock_guard<RankedMutex> lk(log_mu_);
      fprintf(access_log_, "%s\n", line.c_str());
      fflush(access_log_);
    }
  }
}

void StorageServer::NoteTracedMutation(Conn* c, const std::string& remote) {
  if (!c->traced || trace_ == nullptr) return;
  TraceCtx ctx;
  ctx.trace_id = c->trace_ctx.trace_id;
  ctx.parent_span = c->trace_span;  // sync.ship nests under this request
  ctx.flags = c->trace_ctx.flags;
  trace_corr_.Put(remote, ctx);
}

void StorageServer::RespondFile(Conn* c, uint8_t status, int file_fd,
                                int64_t offset, int64_t count) {
  LogAccess(c, status, count);
  c->out.resize(kHeaderSize);
  PutInt64BE(count, reinterpret_cast<uint8_t*>(c->out.data()));
  c->out[8] = static_cast<char>(StorageCmd::kResp);
  c->out[9] = static_cast<char>(status);
  c->out_off = 0;
  c->send_fd = file_fd;
  c->send_off = offset;
  c->send_remaining = count;
  c->state = ConnState::kSend;
  if (!c->async_pending) WriteConn(c);
}

bool StorageServer::WriteConn(Conn* c) {
  for (;;) {
    // 1) buffered bytes
    while (c->out_off < c->out.size()) {
      ssize_t n = send(c->fd, c->out.data() + c->out_off,
                       c->out.size() - c->out_off, MSG_NOSIGNAL);
      if (n > 0) {
        c->out_off += static_cast<size_t>(n);
        continue;
      }
      if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        ConnLoop(c)->Mod(c->fd, EPOLLIN | EPOLLOUT);
        return true;
      }
      if (n < 0 && errno == EINTR) continue;
      CloseConn(c);
      return false;
    }
    // 2) file payload via sendfile
    while (c->send_remaining > 0) {
      off_t off = c->send_off;
      size_t chunk = static_cast<size_t>(
          std::min<int64_t>(c->send_remaining, 1 << 20));
      ssize_t n = sendfile(c->fd, c->send_fd, &off, chunk);
      if (n > 0) {
        c->send_off = off;
        c->send_remaining -= n;
        stats_.bytes_downloaded += n;
        continue;
      }
      if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        ConnLoop(c)->Mod(c->fd, EPOLLIN | EPOLLOUT);
        return true;
      }
      if (n < 0 && errno == EINTR) continue;
      CloseConn(c);
      return false;
    }
    // 3) recipe stream, scatter-gather (PR 5): flush the staged span
    // batch via sendmsg, then refill — cache-hit spans reference the
    // chunk store's shared LRU buffers (zero redundant copies), cold
    // spans pread into the stream's pooled buffer.  A multi-GB chunked
    // download never occupies more than one batch of memory and never
    // stalls this loop's other connections (reference: storage_dio.c
    // reads).
    if (c->rstream != nullptr) {
      RecipeStream* rs = c->rstream.get();
      if (rs->HasPending()) {
        switch (FlushRecipeSpans(c, rs)) {
          case FlushResult::kBlocked:
            ConnLoop(c)->Mod(c->fd, EPOLLIN | EPOLLOUT);
            return true;
          case FlushResult::kError:
            CloseConn(c);
            return false;
          case FlushResult::kDone:
            break;
        }
      }
      if (rs->remaining > 0) {
        if (!RefillRecipeSpans(rs)) {
          CloseConn(c);  // header already sent; abort is the only option
          return false;
        }
        continue;  // flush what we just staged
      }
    }
    break;
  }
  if (c->state == ConnState::kSend) {
    if (c->send_fd >= 0) {
      close(c->send_fd);
      c->send_fd = -1;
    }
    c->rstream.reset();
    if (c->close_after_send) {
      CloseConn(c);
      return false;
    }
    ConnLoop(c)->Mod(c->fd, EPOLLIN);
    ResetForNextRequest(c);
  }
  return true;
}

bool StorageServer::RefillRecipeSpans(RecipeStream* rs) {
  // One round stages up to kBatchBytes across up to kMaxSpans spans —
  // enough to amortize the sendmsg syscall, small enough that a slow
  // client never parks more than ~1 MB per connection (an 8 MB chunk is
  // staged one bounded slice per round; the cache holds the whole chunk
  // so later rounds hit).  Cold spans pread into the pooled buffer,
  // which is sized ONCE per round before any span references it.
  constexpr int64_t kBatchBytes = 1 << 20;
  constexpr size_t kMaxSpans = 64;
  rs->spans.clear();
  rs->span_idx = 0;
  rs->span_off = 0;
  struct ColdRead {
    size_t span;      // index into rs->spans
    size_t entry;     // index into rs->recipe.chunks
    int64_t file_off; // offset inside the chunk payload
  };
  ColdRead cold[kMaxSpans];
  size_t n_cold = 0;
  int64_t staged = 0;
  size_t pool_bytes = 0;
  while (rs->remaining - staged > 0 && rs->spans.size() < kMaxSpans &&
         staged < kBatchBytes) {
    if (rs->idx >= rs->recipe.chunks.size()) {
      FDFS_LOG_ERROR("recipe exhausted with %lld bytes unsent",
                     static_cast<long long>(rs->remaining - staged));
      return false;
    }
    const RecipeEntry& e = rs->recipe.chunks[rs->idx];
    int64_t avail = e.length - rs->skip;
    if (avail <= 0) {  // zero-length or fully-skipped entry
      rs->idx++;
      rs->skip = 0;
      continue;
    }
    int64_t take = std::min(
        {avail, rs->remaining - staged, kBatchBytes - staged});
    RecipeStream::Span sp;
    // Cache path only for chunks that can actually LIVE in the cache:
    // a chunk bigger than the whole cache would be re-read IN FULL on
    // every staging round (the insert is always rejected), so it takes
    // the pooled pread-slice path like the cache-off case.
    std::shared_ptr<const std::string> buf;
    if (rs->cs->cache_enabled() &&
        e.length <= rs->cs->cache_capacity_bytes()) {
      bool hit = false;
      buf = rs->cs->ReadChunkCached(e.digest_hex, e.length, &hit);
      if (buf == nullptr) {
        // Unreadable (missing/short/jailed) — abort the stream.
        FDFS_LOG_ERROR("missing chunk %s mid-download",
                       e.digest_hex.c_str());
        return false;
      }
    }
    if (buf != nullptr) {
      sp.owner = std::move(buf);
      sp.off = static_cast<size_t>(rs->skip);
      sp.len = static_cast<size_t>(take);
    } else {
      sp.off = pool_bytes;
      sp.len = static_cast<size_t>(take);
      cold[n_cold++] = ColdRead{rs->spans.size(), rs->idx, rs->skip};
      pool_bytes += static_cast<size_t>(take);
    }
    rs->spans.push_back(std::move(sp));
    staged += take;
    if (take == avail) {
      rs->idx++;
      rs->skip = 0;
    } else {
      rs->skip += take;  // bounded mid-chunk stop; resume next round
    }
  }
  // The pool is final-sized before any cold read, so span offsets into
  // it stay valid for the whole round.  The whole cold set goes down as
  // ONE batched call: slab-resident spans coalesce into preadv runs
  // (one syscall per contiguous slab extent) instead of one pread per
  // span (ISSUE 18).
  rs->pool.resize(pool_bytes);
  if (n_cold > 0) {
    ChunkStore::SliceReq creqs[kMaxSpans];
    for (size_t i = 0; i < n_cold; ++i) {
      const RecipeEntry& e = rs->recipe.chunks[cold[i].entry];
      RecipeStream::Span& sp = rs->spans[cold[i].span];
      creqs[i] = ChunkStore::SliceReq{&e.digest_hex, cold[i].file_off,
                                      static_cast<int64_t>(sp.len),
                                      rs->pool.data() + sp.off};
    }
    int64_t batches = 0, vec_spans = 0;
    std::string failed;
    bool read_ok =
        rs->cs->ReadChunkSlices(creqs, n_cold, &batches, &vec_spans, &failed);
    if (batches > 0) {
      ctr_dio_preadv_batches_->fetch_add(batches, std::memory_order_relaxed);
      ctr_dio_preadv_spans_->fetch_add(vec_spans, std::memory_order_relaxed);
    }
    if (!read_ok) {
      FDFS_LOG_ERROR("missing chunk %s mid-download", failed.c_str());
      return false;
    }
  }
  rs->remaining -= staged;
  stats_.bytes_downloaded += staged;
  return true;
}

StorageServer::FlushResult StorageServer::FlushRecipeSpans(
    Conn* c, RecipeStream* rs) {
  while (rs->HasPending()) {
    struct iovec iov[64];
    size_t n = 0;
    size_t first_off = rs->span_off;
    for (size_t i = rs->span_idx;
         i < rs->spans.size() && n < sizeof(iov) / sizeof(iov[0]); ++i) {
      const RecipeStream::Span& sp = rs->spans[i];
      const char* base = sp.owner != nullptr ? sp.owner->data() + sp.off
                                             : rs->pool.data() + sp.off;
      iov[n].iov_base = const_cast<char*>(base + first_off);
      iov[n].iov_len = sp.len - first_off;
      first_off = 0;
      ++n;
    }
    struct msghdr msg = {};
    msg.msg_iov = iov;
    msg.msg_iovlen = n;
    ssize_t sent = sendmsg(c->fd, &msg, MSG_NOSIGNAL);
    if (sent <= 0) {
      if (sent < 0 && (errno == EAGAIN || errno == EWOULDBLOCK))
        return FlushResult::kBlocked;
      if (sent < 0 && errno == EINTR) continue;
      return FlushResult::kError;
    }
    size_t left = static_cast<size_t>(sent);
    while (left > 0) {
      RecipeStream::Span& sp = rs->spans[rs->span_idx];
      size_t span_left = sp.len - rs->span_off;
      if (left < span_left) {
        rs->span_off += left;
        left = 0;
      } else {
        left -= span_left;
        sp.owner.reset();  // release the cache ref as soon as it's sent
        rs->span_idx++;
        rs->span_off = 0;
      }
    }
  }
  return FlushResult::kDone;
}

void StorageServer::ReadConn(Conn* c) {
  char buf[kIoBufSize];
  const int fd = c->fd;
  // The owning NioThread outlives every conn; grab the map while `c` is
  // certainly alive (handlers below may free it).
  auto& conns = c->owner->conns;
  for (;;) {
    // Handlers (OnHeaderComplete/OnFixedComplete/OnFileComplete and the
    // Respond path) may CloseConn() and free *c — re-check liveness before
    // every state-machine step.
    auto alive = conns.find(fd);
    if (alive == conns.end() || alive->second.get() != c) return;
    if (c->async_pending) return;  // a dio worker owns this request now
    switch (c->state) {
      case ConnState::kRecvHeader: {
        ssize_t n = recv(c->fd, c->header + c->header_got,
                         kHeaderSize - c->header_got, 0);
        if (n == 0) {
          CloseConn(c);
          return;
        }
        if (n < 0) {
          if (errno == EAGAIN || errno == EWOULDBLOCK) return;
          if (errno == EINTR) continue;
          CloseConn(c);
          return;
        }
        c->header_got += static_cast<size_t>(n);
        if (c->header_got == static_cast<size_t>(kHeaderSize))
          OnHeaderComplete(c);
        break;
      }
      case ConnState::kRecvFixed: {
        size_t want = c->fixed_need - c->fixed.size();
        ssize_t n = recv(c->fd, buf, std::min(want, sizeof(buf)), 0);
        if (n == 0) {
          CloseConn(c);
          return;
        }
        if (n < 0) {
          if (errno == EAGAIN || errno == EWOULDBLOCK) return;
          if (errno == EINTR) continue;
          CloseConn(c);
          return;
        }
        c->fixed.append(buf, static_cast<size_t>(n));
        c->body_consumed += n;
        if (c->fixed.size() == c->fixed_need) OnFixedComplete(c);
        break;
      }
      case ConnState::kRecvFile: {
        size_t want = static_cast<size_t>(
            std::min<int64_t>(c->file_remaining, sizeof(buf)));
        ssize_t n = recv(c->fd, buf, want, 0);
        if (n == 0) {
          CloseConn(c);
          return;
        }
        if (n < 0) {
          if (errno == EAGAIN || errno == EWOULDBLOCK) return;
          if (errno == EINTR) continue;
          CloseConn(c);
          return;
        }
        // Account before any failure handling: these bytes left the socket,
        // so a drain triggered below must not wait for them again.
        c->file_remaining -= n;
        c->body_consumed += n;
        if (!c->discarding) {
          if (c->hashing) {
            c->sha1.Update(buf, static_cast<size_t>(n));
            ctr_recv_hashed_bytes_->fetch_add(n, std::memory_order_relaxed);
          }
          c->crc32 = Crc32(buf, static_cast<size_t>(n), c->crc32);
          ssize_t w = write(c->file_fd, buf, static_cast<size_t>(n));
          if (w != n) {
            FDFS_LOG_ERROR("tmp write failed: %s", strerror(errno));
            RespondError(c, static_cast<uint8_t>(5 /*EIO*/));
            // RespondError flips to drain mode unless the body is already
            // fully consumed, in which case it responded and reset.
            continue;
          }
          stats_.bytes_uploaded += n;
        }
        if (c->file_remaining == 0) {
          OnFileComplete(c);
          // Response path (or a dio worker) takes over; stop reading
          // until reset.  async_pending MUST be tested first: once the
          // job is submitted a worker may already be writing c->state,
          // and only the flag is loop-thread-owned.
          if (c->async_pending || c->state == ConnState::kSend) return;
        }
        break;
      }
      case ConnState::kSend:
        return;  // not reading while a response is in flight
    }
  }
}

// -- dispatch -------------------------------------------------------------

void StorageServer::OnHeaderComplete(Conn* c) {
  c->pkg_len = GetInt64BE(c->header);
  c->cmd = c->header[8];
  // Monotonic clock (a wall-clock/NTP step mid-request would log
  // negative latencies).  Always stamped: the stats registry's
  // per-opcode latency histograms run even without the access log.
  c->req_start_us = MonoUs();
  c->stages.Reset();
  c->shed_resp = false;
  if (c->peer_ip.empty()) c->peer_ip = PeerIp(c->fd);
  if (c->pkg_len < 0) {
    FDFS_LOG_WARN("negative pkg_len from %s", PeerIp(c->fd).c_str());
    CloseConn(c);
    return;
  }
  auto cmd = static_cast<StorageCmd>(c->cmd);
  // Admission consult (ISSUE 19) at the header stage — before any body
  // byte is read, so a shed request costs one drain, not one disk op.
  // Prefix frames (TRACE_CTX / PRIORITY) carry metadata for the NEXT
  // request and are never consulted themselves.  The class comes from a
  // PRIORITY frame when one preceded this header (consumed here) or the
  // opcode-class table; CONTROL survives every ladder rung, so the
  // observability plane stays reachable during the overload it exists
  // to diagnose.
  if (cmd != StorageCmd::kTraceCtx && cmd != StorageCmd::kPriority) {
    uint8_t cls = c->priority != kPriorityUntagged
                      ? c->priority
                      : DefaultPriorityClass(c->cmd);
    c->priority = kPriorityUntagged;  // one frame tags one request
    if (cls > kPriorityBackground) cls = kPriorityBackground;
    c->resolved_priority = cls;
    int64_t retry_ms = 0;
    if (!admission_->AdmitOrShed(cls, &retry_ms)) {
      ShedRequest(c, retry_ms);
      return;
    }
    // Admitted: this request's declared bytes join the in-flight ledger
    // (a pressure signal — bytes accepted but not yet answered).
    c->inflight_acct = c->pkg_len;
    inflight_bytes_.fetch_add(c->inflight_acct, std::memory_order_relaxed);
  }
  switch (cmd) {
    case StorageCmd::kActiveTest:
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0);
      return;
    case StorageCmd::kStat:
      // Observability dump: empty body -> registry JSON snapshot.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0, BuildStatsJson());
      return;
    case StorageCmd::kTraceDump:
      // Span ring dump: empty body -> {"role","port","spans":[...]}.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0, trace_->Json("storage", cfg_.port));
      return;
    case StorageCmd::kEventDump:
      // Flight-recorder dump: empty body -> {"role","port","events":[...]}
      // (fastdfs_tpu.monitor.decode_events; fdfs_codec event-json golden).
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0, events_->Json("storage", cfg_.port));
      return;
    case StorageCmd::kMetricsHistory:
      // Metrics-journal window dump: empty body = everything retained,
      // 8B body = since-ts (epoch µs).  ENOTSUP when journaling is off
      // (metrics_journal_mb = 0) so callers can tell "no journal" from
      // "no history yet".
      if (c->pkg_len != 0 && c->pkg_len != 8) {
        CloseConn(c);
        return;
      }
      if (metrics_ == nullptr) {
        RespondError(c, 95 /*ENOTSUP*/);
        return;
      }
      if (c->pkg_len == 0) {
        // Reading + delta-decoding up to the whole journal ring is file
        // I/O plus CPU that scales with metrics_journal_mb — run it on
        // the dio pool, not this nio loop (a post-mortem query must not
        // itself spike nio.loop_lag_us).
        OffloadToDio(c, 0, [this, c] {
          Respond(c, 0, metrics_->DumpJson("storage", cfg_.port, 0));
        });
        return;
      }
      c->fixed_need = 8;
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kHeatTop:
      // Hot-key top-K dump: empty body = the daemon's heat_top_k,
      // 8B body = explicit k.  ENOTSUP when the sketch is off.
      if (c->pkg_len != 0 && c->pkg_len != 8) {
        CloseConn(c);
        return;
      }
      if (heat_ == nullptr) {
        RespondError(c, 95 /*ENOTSUP*/);
        return;
      }
      if (c->pkg_len == 0) {
        Respond(c, 0, heat_->TopJson("storage", cfg_.port, cfg_.heat_top_k));
        return;
      }
      c->fixed_need = 8;
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kProfileCtl:
      // Profiler control: 17B fixed body = 1B action (1=start, 0=stop)
      // + 8B BE hz + 8B BE duration seconds (protocol.py PROFILE_CTL).
      if (c->pkg_len != 17) {
        CloseConn(c);
        return;
      }
      c->fixed_need = 17;
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kProfileDump:
      // Folded-stack dump: empty body -> JSON (monitor.decode_profile;
      // fdfs_codec profile-json golden).  Aggregation + symbolization
      // walk the whole slab and malloc per frame, so run on the dio
      // pool, not this nio loop (the metrics-history discipline).
      // ENOTSUP while no capture was ever started.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (!Profiler::Global().ever_started()) {
        RespondError(c, 95 /*ENOTSUP*/);
        return;
      }
      OffloadToDio(c, 0, [this, c] {
        std::string j;
        int rc = Profiler::Global().DumpJson("storage", cfg_.port, &j);
        if (rc != 0)
          RespondError(c, static_cast<uint8_t>(rc));
        else
          Respond(c, 0, j);
      });
      return;
    case StorageCmd::kHealthStatus:
      // Gray-failure health table: empty body -> JSON (peer EWMA rows +
      // disk probes + watchdog counts; monitor.decode_health_status;
      // fdfs_codec health-status golden).  One bounded-size snapshot
      // under the health mutex — fine on the nio loop.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0, HealthStatusJson());
      return;
    case StorageCmd::kScrubStatus: {
      // Integrity-engine status: empty body -> kScrubStatCount BE int64
      // slots (kScrubStatNames).  Atomics + per-store gauge reads only,
      // so serving it on the nio loop is fine.  ENOTSUP without a chunk
      // store — there is nothing to scrub.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (scrub_ == nullptr) {
        Respond(c, 95 /*ENOTSUP*/);
        return;
      }
      int64_t vals[kScrubStatCount] = {0};
      scrub_->FillStats(vals);
      std::string body(kScrubStatCount * 8, '\0');
      for (int i = 0; i < kScrubStatCount; ++i)
        PutInt64BE(vals[i], reinterpret_cast<uint8_t*>(body.data()) + i * 8);
      Respond(c, 0, body);
      return;
    }
    case StorageCmd::kScrubKick:
      // Force a verify+repair+GC pass (works even with periodic
      // scrubbing off).  The kick only flips a flag under the scrub
      // mutex — the pass itself runs on the scrub thread.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (scrub_ == nullptr) {
        Respond(c, 95 /*ENOTSUP*/);
        return;
      }
      scrub_->Kick();
      Respond(c, 0);
      return;
    case StorageCmd::kEcStatus: {
      // Cold-tier status: empty body -> kEcStatCount BE int64 slots
      // (kEcStatNames).  ENOTSUP when the tier is off AND no drained
      // stripes exist — same shape as SCRUB_STATUS.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (scrub_ == nullptr || (cfg_.ec_k <= 0 && scrub_->EcStatValue(0) == 0)) {
        Respond(c, 95 /*ENOTSUP*/);
        return;
      }
      int64_t vals[kEcStatCount] = {0};
      scrub_->FillEcStats(vals);
      std::string body(kEcStatCount * 8, '\0');
      for (int i = 0; i < kEcStatCount; ++i)
        PutInt64BE(vals[i], reinterpret_cast<uint8_t*>(body.data()) + i * 8);
      Respond(c, 0, body);
      return;
    }
    case StorageCmd::kEcKick:
      // Force a scrub pass whose demote stage ignores the age gate —
      // the operator's "drain the replicated tier NOW" lever.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (scrub_ == nullptr || cfg_.ec_k <= 0) {
        Respond(c, 95 /*ENOTSUP*/);
        return;
      }
      scrub_->EcKick();
      Respond(c, 0);
      return;
    case StorageCmd::kTraceCtx:
      // Trace-context prefix frame: 16B body, NO response; the context
      // applies to the next request on this connection.  A wrong length
      // cannot be resynced mid-stream — close.
      if (c->pkg_len != kTraceCtxLen) {
        CloseConn(c);
        return;
      }
      c->fixed_need = static_cast<size_t>(kTraceCtxLen);
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kPriority:
      // Priority prefix frame (the TRACE_CTX pattern): 1B class, NO
      // response; tags the next request on this connection.  A wrong
      // length cannot be resynced mid-stream — close.
      if (c->pkg_len != kPriorityFrameLen) {
        CloseConn(c);
        return;
      }
      c->fixed_need = static_cast<size_t>(kPriorityFrameLen);
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kQueryChunking:
      // How this node cuts: empty body -> six BE int64 slots
      // (PackChunkingParams).  Conf values only: fine on the nio loop.
      // ENOTSUP without a chunk store: no recipe can be stored here.
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      if (dedup_ == nullptr || chunk_stores_.empty()) {
        // The client uploads plain: a fallback this node can see.
        if (ctr_ingest_fallbacks_ != nullptr)
          ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
        Respond(c, 95 /*ENOTSUP*/);
      } else {
        Respond(c, 0,
                PackChunkingParams(cfg_.cdc_widths, cfg_.dedup_chunk_threshold,
                                   cfg_.dedup_segment_bytes));
      }
      return;
    case StorageCmd::kAdmissionStatus:
      // Admission-controller state dump: empty body -> JSON (ladder
      // level, pressure/EWMA, per-class shed counts;
      // monitor.decode_admission; fdfs_codec admission-json golden).
      if (c->pkg_len != 0) {
        CloseConn(c);
        return;
      }
      Respond(c, 0, admission_->StatusJson("storage", cfg_.port));
      return;
    case StorageCmd::kUploadFile:
    case StorageCmd::kUploadAppenderFile:
      stats_.total_upload++;
      // Placement drain (ISSUE 11): a draining group takes no NEW
      // files — EBUSY sends the client back to the tracker, which no
      // longer routes stores here.  Replication (kSync*) and the
      // rebalance migrator's loopback reads/deletes stay allowed.
      if (DrainingRefusal()) {
        RespondError(c, 16 /*EBUSY*/);
        return;
      }
      if (c->pkg_len < 15) {
        RespondError(c, 22 /*EINVAL*/);
        return;
      }
      c->fixed_need = 15;  // 1B spi + 8B size + 6B ext
      c->state = ConnState::kRecvFixed;
      return;
    case StorageCmd::kSyncCreateFile:
      c->fixed_need = 32;  // 16B group + 8B name_len + 8B size, then name
      break;
    case StorageCmd::kSyncCreateRecipe:
      // 16B group + 8B name_len + 8B logical + 8B chunk_count +
      // 8B payload_len, then name + chunk entries (inline), then the
      // missing-chunk payloads (streamed to a tmp file).
      c->fixed_need = 48;
      break;
    case StorageCmd::kSyncAppendFile:
    case StorageCmd::kSyncModifyFile:
      c->fixed_need = 40;  // 16B group + 8B name_len + 8B off + 8B len, name
      break;
    case StorageCmd::kUploadChunks:
      // Negotiated upload phase 2: 8B session + 8B payload_len, then the
      // missing-chunk payloads (streamed to a tmp file).
      stats_.total_upload++;
      c->fixed_need = 16;
      break;
    case StorageCmd::kAppendFile:
      stats_.total_append++;
      c->fixed_need = 32;  // 16B group + 8B name_len + 8B append_len, name
      break;
    case StorageCmd::kModifyFile:
      stats_.total_append++;
      c->fixed_need = 40;  // 16B group + 8B name_len + 8B off + 8B len, name
      break;
    case StorageCmd::kUploadSlaveFile:
      stats_.total_upload++;
      if (DrainingRefusal()) {  // drain: no new files (see kUploadFile)
        RespondError(c, 16 /*EBUSY*/);
        return;
      }
      // 16B group + 8B master_len + 8B size + 16B prefix + 6B ext, master
      c->fixed_need = 16 + 8 + 8 + 16 + 6;
      break;
    case StorageCmd::kDownloadFile:
    case StorageCmd::kDeleteFile:
    case StorageCmd::kQueryFileInfo:
    case StorageCmd::kNearDups:
    case StorageCmd::kSetMetadata:
    case StorageCmd::kGetMetadata:
    case StorageCmd::kSyncDeleteFile:
    case StorageCmd::kSyncCreateLink:
    case StorageCmd::kSyncUpdateFile:
    case StorageCmd::kSyncTruncateFile:
    case StorageCmd::kSyncQueryChunks:
    case StorageCmd::kFetchRecipe:
    case StorageCmd::kFetchChunk:
    case StorageCmd::kUploadRecipe:
    case StorageCmd::kTruncateFile:
    case StorageCmd::kCreateLink:
    case StorageCmd::kTrunkAllocSpace:
    case StorageCmd::kTrunkAllocConfirm:
    case StorageCmd::kTrunkFreeSpace:
    case StorageCmd::kFetchOnePathBinlog:
    case StorageCmd::kEcRelease:
      if (c->pkg_len > kMaxInlineBody) {
        CloseConn(c);
        return;
      }
      c->fixed_need = static_cast<size_t>(c->pkg_len);
      if (c->fixed_need == 0) {
        Respond(c, 22 /*EINVAL*/);
        return;
      }
      c->state = ConnState::kRecvFixed;
      return;
    default:
      FDFS_LOG_WARN("unknown cmd %d from %s", c->cmd, PeerIp(c->fd).c_str());
      RespondError(c, 22 /*EINVAL*/);
      return;
  }
  // Fixed-prefix commands that broke out of the switch: the declared body
  // must at least cover the fixed prefix, or the reader would swallow the
  // next pipelined request's header as fixed data (protocol desync).
  if (c->pkg_len < static_cast<int64_t>(c->fixed_need)) {
    RespondError(c, 22 /*EINVAL*/);
    return;
  }
  c->state = ConnState::kRecvFixed;
}

void StorageServer::OnFixedComplete(Conn* c) {
  auto cmd = static_cast<StorageCmd>(c->cmd);
  switch (cmd) {
    case StorageCmd::kTraceCtx: {
      // Stash the context and allocate the next request's root span id
      // (mutation paths correlate through it before LogAccess records
      // the span).  Minimal reset — NOT ResetForNextRequest, which
      // clears the trace fields — then keep reading: the very next
      // bytes are the traced request's header.
      c->trace_ctx =
          ParseTraceCtx(reinterpret_cast<const uint8_t*>(c->fixed.data()));
      c->traced = c->trace_ctx.valid();
      c->trace_span = c->traced ? trace_->NextSpanId() : 0;
      c->state = ConnState::kRecvHeader;
      c->header_got = 0;
      c->fixed.clear();
      c->fixed_need = 0;
      c->pkg_len = 0;
      c->body_consumed = 0;
      c->req_start_us = 0;
      return;
    }
    case StorageCmd::kPriority: {
      // Stash the class for the next request (out-of-range bytes clamp
      // to background — garbage priority must never OUTRANK honest
      // traffic).  Minimal reset like kTraceCtx: the very next bytes
      // are the tagged request's header.
      uint8_t cls = static_cast<uint8_t>(c->fixed[0]);
      c->priority = cls > kPriorityBackground ? kPriorityBackground : cls;
      c->state = ConnState::kRecvHeader;
      c->header_got = 0;
      c->fixed.clear();
      c->fixed_need = 0;
      c->pkg_len = 0;
      c->body_consumed = 0;
      c->req_start_us = 0;
      return;
    }
    case StorageCmd::kMetricsHistory: {
      int64_t since = GetInt64BE(
          reinterpret_cast<const uint8_t*>(c->fixed.data()));
      // Journal read + decode off the nio loop, like the empty-body path.
      OffloadToDio(c, 0, [this, c, since] {
        Respond(c, 0, metrics_->DumpJson("storage", cfg_.port,
                                         since < 0 ? 0 : since));
      });
      return;
    }
    case StorageCmd::kHeatTop: {
      int64_t k = GetInt64BE(
          reinterpret_cast<const uint8_t*>(c->fixed.data()));
      if (k <= 0 || k > 65536) k = cfg_.heat_top_k;
      Respond(c, 0, heat_->TopJson("storage", cfg_.port,
                                   static_cast<int>(k)));
      return;
    }
    case StorageCmd::kProfileCtl: {
      const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
      uint8_t action = p[0];
      int64_t hz = GetInt64BE(p + 1);
      int64_t secs = GetInt64BE(p + 9);
      int rc;
      if (action == 1) {
        // Range guard before the int narrowing; Start clamps to
        // profile_max_hz / kMaxDurationS on top of this.
        if (hz <= 0 || hz > 100000 || secs <= 0 || secs > 86400)
          rc = 22;
        else
          rc = Profiler::Global().Start(static_cast<int>(hz),
                                        static_cast<int>(secs));
      } else if (action == 0) {
        rc = Profiler::Global().Stop();
      } else {
        rc = 22;
      }
      if (rc != 0) {
        RespondError(c, static_cast<uint8_t>(rc));
        return;
      }
      // Ack with what actually took effect (hz may have been clamped).
      Profiler& prof = Profiler::Global();
      Respond(c, 0,
              std::string("{\"active\":") + (prof.active() ? "true" : "false") +
                  ",\"hz\":" + std::to_string(prof.armed_hz()) + "}");
      return;
    }
    case StorageCmd::kUploadFile:
    case StorageCmd::kUploadAppenderFile:
      if (!BeginUpload(c)) return;
      c->state = ConnState::kRecvFile;
      if (c->file_remaining == 0) OnFileComplete(c);  // zero-byte upload
      return;
    case StorageCmd::kSyncCreateFile: {
      // Two-stage fixed read: prefix then name.
      const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
      int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
      int64_t size = GetInt64BE(p + kGroupNameMaxLen + 8);
      if (c->fixed.size() == 32) {
        if (name_len <= 0 || name_len > 512 || size < 0 ||
            c->pkg_len != 32 + name_len + size) {
          RespondError(c, 22);
          return;
        }
        c->fixed_need = 32 + static_cast<size_t>(name_len);
        return;  // keep reading the name
      }
      std::string group = GroupFromField(p);
      c->sync_remote = c->fixed.substr(32);
      c->file_size = size;
      c->file_remaining = size;
      if (group != cfg_.group_name ||
          !LocalPath(store_.store_path(0), c->sync_remote).has_value()) {
        RespondError(c, 22);
        return;
      }
      int spi = 0;
      sscanf(c->sync_remote.c_str(), "M%02X/", &spi);
      if (spi >= store_.store_path_count()) {
        RespondError(c, 22);
        return;
      }
      c->store_path_index = spi;
      c->tmp_path = store_.NewTmpPath(spi);
      c->file_fd = open(c->tmp_path.c_str(), O_CREAT | O_WRONLY | O_TRUNC, 0644);
      if (c->file_fd < 0) {
        RespondError(c, 5);
        return;
      }
      c->state = ConnState::kRecvFile;
      if (c->file_remaining == 0) OnFileComplete(c);
      return;
    }
    case StorageCmd::kSyncCreateRecipe: {
      const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
      int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
      int64_t logical = GetInt64BE(p + kGroupNameMaxLen + 8);
      int64_t n_chunks = GetInt64BE(p + kGroupNameMaxLen + 16);
      int64_t payload = GetInt64BE(p + kGroupNameMaxLen + 24);
      if (c->fixed.size() == 48) {
        if (name_len <= 0 || name_len > 512 || logical < 0 ||
            n_chunks <= 0 || n_chunks > (1 << 22) || payload < 0 ||
            c->pkg_len != 48 + name_len + n_chunks * 29 + payload ||
            48 + name_len + n_chunks * 29 > kMaxInlineBody) {
          RespondError(c, 22);
          return;
        }
        c->fixed_need = static_cast<size_t>(48 + name_len + n_chunks * 29);
        return;  // keep reading name + chunk entries
      }
      std::string group = GroupFromField(p);
      c->sync_remote = c->fixed.substr(48, static_cast<size_t>(name_len));
      c->file_size = payload;
      c->file_remaining = payload;
      if (group != cfg_.group_name ||
          !LocalPath(store_.store_path(0), c->sync_remote).has_value()) {
        RespondError(c, 22);
        return;
      }
      int spi = 0;
      sscanf(c->sync_remote.c_str(), "M%02X/", &spi);
      if (spi >= store_.store_path_count() ||
          spi >= static_cast<int>(chunk_stores_.size())) {
        RespondError(c, 95 /*ENOTSUP: no chunk store for this path*/);
        return;
      }
      c->store_path_index = spi;
      c->tmp_path = store_.NewTmpPath(spi);
      c->file_fd = open(c->tmp_path.c_str(), O_CREAT | O_WRONLY | O_TRUNC,
                        0644);
      if (c->file_fd < 0) {
        RespondError(c, 5);
        return;
      }
      c->state = ConnState::kRecvFile;
      if (c->file_remaining == 0) OnFileComplete(c);
      return;
    }
    case StorageCmd::kSyncAppendFile:
    case StorageCmd::kSyncModifyFile:
      if (!BeginSyncRange(c)) return;
      if (c->state == ConnState::kRecvFile && c->file_remaining == 0)
        OnFileComplete(c);
      return;
    case StorageCmd::kAppendFile:
    case StorageCmd::kModifyFile:
      if (!BeginClientRange(c)) return;
      if (c->state == ConnState::kRecvFile && c->file_remaining == 0)
        OnFileComplete(c);
      return;
    case StorageCmd::kUploadSlaveFile:
      if (!BeginSlaveUpload(c)) return;
      if (c->state == ConnState::kRecvFile && c->file_remaining == 0)
        OnFileComplete(c);
      return;
    case StorageCmd::kSyncUpdateFile:
      HandleSyncUpdate(c);
      return;
    case StorageCmd::kSyncTruncateFile:
    case StorageCmd::kTruncateFile:
      HandleTruncate(c);
      return;
    case StorageCmd::kDownloadFile:
      HandleDownload(c);
      return;
    case StorageCmd::kDeleteFile:
    case StorageCmd::kSyncDeleteFile:
      HandleDelete(c);
      return;
    case StorageCmd::kQueryFileInfo:
      HandleQueryFileInfo(c);
      return;
    case StorageCmd::kNearDups:
      HandleNearDups(c);
      return;
    case StorageCmd::kSetMetadata:
      HandleSetMetadata(c);
      return;
    case StorageCmd::kGetMetadata:
      HandleGetMetadata(c);
      return;
    case StorageCmd::kTrunkAllocSpace:
    case StorageCmd::kTrunkAllocConfirm:
    case StorageCmd::kTrunkFreeSpace:
      HandleTrunkRpc(c);
      return;
    case StorageCmd::kFetchOnePathBinlog:
      HandleFetchOnePathBinlog(c);
      return;
    case StorageCmd::kSyncCreateLink:
    case StorageCmd::kCreateLink:
      HandleCreateLink(c);
      return;
    case StorageCmd::kSyncQueryChunks:
      HandleSyncQueryChunks(c);
      return;
    case StorageCmd::kFetchRecipe: {
      // Up to 16 MB of chunk/recipe disk reads per request: run on the
      // file's store-path dio pool, not this nio event loop (a slow disk
      // would stall every other connection on the loop).
      int spi = 0;
      if (c->fixed.size() >= 16 + 4)
        sscanf(c->fixed.c_str() + 16, "M%02X/", &spi);
      OffloadToDio(c, spi, [this, c] { HandleFetchRecipe(c); });
      return;
    }
    case StorageCmd::kFetchChunk: {
      int spi = 0;
      if (c->fixed.size() >= 24 + 4)
        sscanf(c->fixed.c_str() + 24, "M%02X/", &spi);
      OffloadToDio(c, spi, [this, c] { HandleFetchChunk(c); });
      return;
    }
    case StorageCmd::kEcRelease:
      // Chunk-store drops + released.log fsync — dio work.  Releases
      // are digest-addressed (no store-path routing: each store drops
      // what it holds), so pool 0 serializes them, which is fine for a
      // scrub-paced background RPC.
      OffloadToDio(c, 0, [this, c] { HandleEcRelease(c); });
      return;
    case StorageCmd::kUploadRecipe: {
      // Drain refusal at session START only: an in-flight session's
      // kUploadChunks may still commit (the file predates the drain
      // decision and migrates with everything else).
      if (DrainingRefusal()) {
        Respond(c, 16 /*EBUSY*/);
        return;
      }
      // Chunk-store probe + pin: cheap, but it contends on the store
      // mutex with every concurrent upload's PutAndRef — keep it off
      // the nio loop like the other chunk-store servers.
      int spi = c->fixed.empty() ? 0 : static_cast<uint8_t>(c->fixed[0]);
      OffloadToDio(c, spi == 0xFF ? 0 : spi,
                   [this, c] { HandleUploadRecipe(c); });
      return;
    }
    case StorageCmd::kUploadChunks:
      if (!BeginUploadChunks(c)) return;
      if (c->file_remaining == 0) OnFileComplete(c);  // all chunks present
      return;
    default:
      Respond(c, 22);
      return;
  }
}

void StorageServer::OnFileComplete(Conn* c) {
  // recv-stage end (access log AND spans)
  c->stages.Add(Stage::kRecv, c->req_start_us, MonoUs());
  if (c->discarding) {  // rejected request: body drained, send the verdict
    Respond(c, c->pending_status, c->pending_body);
    return;
  }
  auto cmd = static_cast<StorageCmd>(c->cmd);
  if (cmd == StorageCmd::kSyncAppendFile || cmd == StorageCmd::kSyncModifyFile ||
      cmd == StorageCmd::kAppendFile || cmd == StorageCmd::kModifyFile) {
    close(c->file_fd);
    c->file_fd = -1;
    ReleaseBusy(c);
    char extra[48];
    snprintf(extra, sizeof(extra), "%lld %lld",
             static_cast<long long>(c->range_offset),
             static_cast<long long>(c->file_size));
    bool append =
        cmd == StorageCmd::kSyncAppendFile || cmd == StorageCmd::kAppendFile;
    bool source =
        cmd == StorageCmd::kAppendFile || cmd == StorageCmd::kModifyFile;
    binlog_.Append(source ? (append ? kBinlogOpAppend : kBinlogOpModify)
                          : (append ? 'a' : 'm'),
                   c->sync_remote, extra);
    if (source) {
      stats_.success_append++;
      stats_.last_source_update = time(nullptr);
    }
    Respond(c, 0);
    return;
  }
  // Heavy completions — dedup fingerprinting (a TPU RPC in sidecar
  // mode), chunk-store writes, trunk allocation RPCs, renames — run on
  // the store path's dio pool so no single upload stalls this loop's
  // other connections (reference: the nio→dio handoff in
  // storage_service.c:storage_write_to_file()).
  OffloadToDio(c, c->store_path_index, [this, c] {
    auto wcmd = static_cast<StorageCmd>(c->cmd);
    if (wcmd == StorageCmd::kUploadSlaveFile)
      FinishSlaveUpload(c);
    else if (wcmd == StorageCmd::kSyncCreateFile)
      SyncCreateComplete(c);
    else if (wcmd == StorageCmd::kSyncCreateRecipe)
      SyncRecipeComplete(c);
    else if (wcmd == StorageCmd::kUploadChunks)
      UploadChunksComplete(c);
    else
      FinishUpload(c);
  });
}

void StorageServer::SyncCreateComplete(Conn* c) {
  {
    // Replica write: place at the exact remote filename from the source.
    close(c->file_fd);
    c->file_fd = -1;
    auto tparts = DecodeFileId(cfg_.group_name + "/" + c->sync_remote);
    if (tparts.has_value() && tparts->trunk_loc.has_value()) {
      // Trunk replica: same (id, offset) slot as the source — the ID
      // encodes the location, so layouts must match byte-for-byte.
      // Staleness guard: if the slot already holds a DIFFERENT live file
      // (it was freed via the allocator RPC and reused before this replay
      // arrived), this create is for an already-deleted file — skip it
      // rather than clobber the new occupant.
      {
        std::string tp = TrunkFilePath(store_.store_path(0),
                                       tparts->trunk_loc->trunk_id);
        int gfd = open(tp.c_str(), O_RDONLY);
        if (gfd >= 0) {
          auto gh = ReadSlotHeader(gfd, tparts->trunk_loc->offset);
          close(gfd);
          if (gh.has_value() && gh->type == kTrunkSlotData &&
              gh->file_size != 0 &&
              (gh->file_size != tparts->file_size ||
               gh->crc32 != tparts->crc32)) {
            FDFS_LOG_WARN("stale trunk create %s skipped (slot reused)",
                          c->sync_remote.c_str());
            unlink(c->tmp_path.c_str());
            Respond(c, 0);
            return;
          }
        }
      }
      std::string payload, err;
      if (!ReadWholeFile(c->tmp_path, &payload) ||
          !WriteSlotPayload(store_.store_path(0), *tparts->trunk_loc,
                            payload, tparts->crc32, &err)) {
        FDFS_LOG_ERROR("trunk replica write %s: %s", c->sync_remote.c_str(),
                       err.c_str());
        unlink(c->tmp_path.c_str());
        Respond(c, 5);
        return;
      }
      unlink(c->tmp_path.c_str());
      binlog_.Append('c', c->sync_remote);
      Respond(c, 0);
      return;
    }
    std::string local = ResolveLocal(cfg_.group_name, c->sync_remote);
    if (local.empty()) {
      unlink(c->tmp_path.c_str());
      Respond(c, 22);
      return;
    }
    // Replicas dedup too: chunk-eligible synced files go through the
    // chunk store (same cut-points cluster-wide), others stay flat.
    // Appenders stay flat everywhere (mutable: later SYNC_APPEND/MODIFY
    // ops open the flat file in place — a recipe would break them).
    // Parent dirs only materialize when a flat inode is written (the
    // recipe store handles its own sidecar): slab-resident replicas
    // must cost zero fan-out directories too.
    struct stat st;
    if (!(tparts.has_value() && tparts->appender) &&
        stat(c->tmp_path.c_str(), &st) == 0 && ChunkEligible(st.st_size)) {
      int spi = 0;
      sscanf(c->sync_remote.c_str(), "M%02X/", &spi);
      int64_t saved = 0, hits = 0;
      if (StoreChunked(IngestFor(spi, dedup_.get(), &c->stages), c->tmp_path,
                       st.st_size, local + ".rcp",
                       cfg_.group_name + "/" + c->sync_remote, &saved,
                       &hits)) {
        unlink(c->tmp_path.c_str());
        stats_.dedup_hits += hits;
        stats_.dedup_bytes_saved += saved;
        binlog_.Append('c', c->sync_remote);
        Respond(c, 0);
        return;
      }
    }
    StoreManager::EnsureParentDirs(local);
    if (rename(c->tmp_path.c_str(), local.c_str()) != 0) {
      unlink(c->tmp_path.c_str());
      Respond(c, 5);
      return;
    }
    binlog_.Append('c', c->sync_remote);
    Respond(c, 0);
    return;
  }
}

// FETCH_RECIPE (128): serve a recipe-stored file's chunk list to a
// rebuilding peer (chunk-aware disk recovery).  ENOENT when the file is
// flat/absent — the caller downloads logical bytes instead.
void StorageServer::HandleFetchRecipe(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() <= kGroupNameMaxLen) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  std::string remote = c->fixed.substr(kGroupNameMaxLen);
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  auto r = LoadRecipeFor(local);
  if (!r.has_value()) {
    Respond(c, 2 /*ENOENT: flat or gone*/);
    return;
  }
  // The client rejects recipe bodies over its 64 MB cap; don't build a
  // multi-hundred-MB response it will discard (it falls back to the
  // streamed full download for such files either way).
  if (16 + r->chunks.size() * 28 > (48u << 20)) {
    Respond(c, 2);
    return;
  }
  std::string body;
  uint8_t num[8];
  PutInt64BE(r->logical_size, num);
  body.append(reinterpret_cast<char*>(num), 8);
  PutInt64BE(static_cast<int64_t>(r->chunks.size()), num);
  body.append(reinterpret_cast<char*>(num), 8);
  for (const RecipeEntry& e : r->chunks) {
    if (!HexToBytes(e.digest_hex, &body)) {
      Respond(c, 5);
      return;
    }
    PutInt64BE(e.length, num);
    body.append(reinterpret_cast<char*>(num), 8);
  }
  Respond(c, 0, body);
}

// FETCH_CHUNK (129): serve a BATCH of chunk payloads by digest
// (chunk-aware disk recovery; one round-trip per ~8 MB of missing
// bytes, not one per chunk).  ENOENT when any requested chunk is gone
// — the caller falls back to a full download of that file.
void StorageServer::HandleFetchChunk(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < kGroupNameMaxLen + 16 + 1 + 28) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  size_t base = kGroupNameMaxLen + 8;
  if (group != cfg_.group_name || name_len <= 0 || name_len > 512 ||
      c->fixed.size() < base + name_len + 8) {
    Respond(c, 22);
    return;
  }
  std::string remote = c->fixed.substr(base, static_cast<size_t>(name_len));
  NoteHeat(c, HeatOp::kFetchChunk, group + "/" + remote);
  int spi = 0;
  sscanf(remote.c_str(), "M%02X/", &spi);
  if (spi >= static_cast<int>(chunk_stores_.size())) {
    Respond(c, 95 /*ENOTSUP*/);
    return;
  }
  const uint8_t* q = p + base + name_len;
  int64_t count = GetInt64BE(q);
  if (count <= 0 ||
      static_cast<size_t>(count) !=
          (c->fixed.size() - base - name_len - 8) / 28 ||
      (c->fixed.size() - base - name_len - 8) % 28 != 0) {
    Respond(c, 22);
    return;
  }
  int64_t total = 0;
  for (int64_t i = 0; i < count; ++i) {
    int64_t len = GetInt64BE(q + 8 + i * 28 + 20);
    if (len <= 0 || len > kMaxChunkPayload) {
      Respond(c, 22);
      return;
    }
    total += len;
  }
  if (total > (16 << 20)) {  // batch cap: bounded response memory
    Respond(c, 22);
    return;
  }
  std::string out;
  out.reserve(static_cast<size_t>(total));
  std::string one;
  for (int64_t i = 0; i < count; ++i) {
    const uint8_t* e = q + 8 + i * 28;
    std::string dig = BytesToHex(e, 20);
    int64_t len = GetInt64BE(e + 20);
    // Consult the hot-chunk cache (lookup only — recovery/repair sweeps
    // must not evict client-hot chunks by populating it).
    if (auto cached = chunk_stores_[spi]->CacheLookup(dig, len)) {
      out += *cached;
      continue;
    }
    if (!chunk_stores_[spi]->ReadChunk(dig, len, &one)) {
      Respond(c, 2 /*ENOENT*/);
      return;
    }
    out += one;
  }
  if (ctr_chunkfetch_batches_ != nullptr) {
    ctr_chunkfetch_batches_->fetch_add(1, std::memory_order_relaxed);
    ctr_chunkfetch_chunks_->fetch_add(count, std::memory_order_relaxed);
    ctr_chunkfetch_bytes_->fetch_add(total, std::memory_order_relaxed);
  }
  Respond(c, 0, out);
}

// EC_RELEASE (145): a group peer finished encoding these chunks into a
// verified RS stripe — drop this node's replicated copies.  Body: 16B
// group + 8B count + count x (20B raw digest + 8B BE length); response
// is count bytes (0 = released, 1 = kept — pinned or quarantined
// chunks retain full-replica coverage here, which the owner treats as
// safe over-replication).  The drop is journaled to released.log (one
// fsync'd batch append) BEFORE the response, so a restart rebuilds the
// released marks and reads keep routing to the owner.
void StorageServer::HandleEcRelease(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < kGroupNameMaxLen + 8) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  int64_t count = GetInt64BE(p + kGroupNameMaxLen);
  size_t base = kGroupNameMaxLen + 8;
  if (group != cfg_.group_name || count <= 0 ||
      static_cast<size_t>(count) != (c->fixed.size() - base) / 28 ||
      (c->fixed.size() - base) % 28 != 0) {
    Respond(c, 22);
    return;
  }
  if (chunk_stores_.empty()) {
    Respond(c, 95 /*ENOTSUP*/);
    return;
  }
  std::vector<ChunkStore::ChunkInfo> chunks;
  chunks.reserve(static_cast<size_t>(count));
  for (int64_t i = 0; i < count; ++i) {
    const uint8_t* e = p + base + i * 28;
    ChunkStore::ChunkInfo info;
    info.digest_hex = BytesToHex(e, 20);
    info.length = GetInt64BE(e + 20);
    chunks.push_back(std::move(info));
  }
  // Digest-addressed: every store drops what it holds; a digest kept by
  // ANY store answers kept (the owner may not reclaim its coverage).
  std::string mask(static_cast<size_t>(count), '\0');
  for (auto& cs : chunk_stores_) {
    std::string m = cs->ReleaseChunks(chunks);
    for (int64_t i = 0; i < count && i < static_cast<int64_t>(m.size()); ++i)
      if (m[static_cast<size_t>(i)]) mask[static_cast<size_t>(i)] = 1;
  }
  Respond(c, 0, mask);
}

// Remote read of a released chunk: round-robin the group peers with a
// single-chunk FETCH_CHUNK.  The stripe owner's ReadChunk falls through
// to its EC tier, so this works whichever peer holds the stripe; the
// payload is SHA1-gated by the caller (ChunkStore::ReadChunk).
bool StorageServer::FetchChunkFromPeers(int spi,
                                        const std::string& digest_hex,
                                        int64_t len, std::string* out) {
  if (len <= 0 || sync_ == nullptr) return false;
  char remote[16];
  snprintf(remote, sizeof(remote), "M%02X/ecread", spi);
  std::string body;
  PutFixedField(&body, cfg_.group_name, kGroupNameMaxLen);
  uint8_t num[8];
  PutInt64BE(static_cast<int64_t>(strlen(remote)), num);
  body.append(reinterpret_cast<char*>(num), 8);
  body += remote;
  PutInt64BE(1, num);
  body.append(reinterpret_cast<char*>(num), 8);
  if (!HexToBytes(digest_hex, &body)) return false;
  PutInt64BE(len, num);
  body.append(reinterpret_cast<char*>(num), 8);
  for (const SyncPeerState& s : sync_->States()) {
    size_t colon = s.addr.rfind(':');
    if (colon == std::string::npos) continue;
    std::string err;
    int fd = TcpConnect(s.addr.substr(0, colon),
                        atoi(s.addr.c_str() + colon + 1), 3000, &err);
    if (fd < 0) continue;
    std::string resp;
    uint8_t status = 0;
    bool ok = NetRpc(fd, static_cast<uint8_t>(StorageCmd::kFetchChunk), body,
                     &resp, &status, len + 1024, cfg_.network_timeout_ms);
    close(fd);
    if (!ok || status != 0 || static_cast<int64_t>(resp.size()) != len)
      continue;
    out->swap(resp);
    return true;
  }
  return false;
}

// SYNC_QUERY_CHUNKS (126): which of these digests does this node's
// chunk store lack?  Phase 1 of chunk-aware replication; response body
// is one byte per digest (0 = present, 1 = needed).
void StorageServer::HandleSyncQueryChunks(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < kGroupNameMaxLen + 8) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  size_t base = kGroupNameMaxLen + 8;
  if (group != cfg_.group_name || name_len <= 0 || name_len > 512 ||
      c->fixed.size() < base + name_len ||
      (c->fixed.size() - base - name_len) % 20 != 0) {
    Respond(c, 22);
    return;
  }
  std::string remote = c->fixed.substr(base, static_cast<size_t>(name_len));
  int spi = 0;
  sscanf(remote.c_str(), "M%02X/", &spi);
  if (spi >= static_cast<int>(chunk_stores_.size())) {
    Respond(c, 95 /*ENOTSUP: no chunk store*/);
    return;
  }
  ChunkStore* cs = chunk_stores_[spi].get();
  size_t n = (c->fixed.size() - base - name_len) / 20;
  const uint8_t* digs = p + base + name_len;
  std::vector<std::string> hex;
  hex.reserve(n);
  for (size_t i = 0; i < n; ++i) hex.push_back(BytesToHex(digs + i * 20, 20));
  Respond(c, 0, cs->HaveMask(hex));
}

// UPLOAD_RECIPE (132): phase 1 of the dedup-aware negotiated upload.
// The client chunked + fingerprinted locally; answer which chunks it
// must ship (1 = needed), pin every present chunk so a concurrent
// delete cannot unlink it before phase 2 references it, and park the
// session.  ENOTSUP when this daemon has no chunk store — the client
// falls back to a plain UPLOAD_FILE (an older daemon without this
// opcode answers EINVAL, same client reaction).
void StorageServer::HandleUploadRecipe(Conn* c) {
  // The whole handler up to the reply: parse + PinAndMask (a refusal
  // closes it after the answer was logged, and logs 0).
  StageScope negotiate(&c->stages, Stage::kNegotiate);
  if (dedup_ == nullptr || chunk_stores_.empty()) {
    if (ctr_ingest_fallbacks_ != nullptr)
      ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
    Respond(c, 95 /*ENOTSUP*/);
    return;
  }
  // body: 1B spi + 6B ext + 8B crc32 + 8B logical + 8B count + entries
  constexpr size_t kPrefix = 1 + kFileExtNameMaxLen + 8 + 8 + 8;
  if (c->fixed.size() < kPrefix + 28) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int spi = p[0];
  std::string ext = ExtFromField(p + 1);
  uint32_t crc = static_cast<uint32_t>(GetInt64BE(p + 7));
  int64_t logical = GetInt64BE(p + 15);
  int64_t n_chunks = GetInt64BE(p + 23);
  if (spi == 0xFF) spi = store_.PickStorePath();
  if (spi >= store_.store_path_count() ||
      spi >= static_cast<int>(chunk_stores_.size())) {
    Respond(c, 95 /*ENOTSUP: no chunk store for this path*/);
    return;
  }
  if (logical < cfg_.dedup_chunk_threshold) {
    // Server-authoritative chunking threshold (the plain path's
    // ChunkEligible gate): a payload the daemon would store flat has no
    // recipe to negotiate over.  ENOTSUP => the client falls back.
    if (ctr_ingest_fallbacks_ != nullptr)
      ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
    Respond(c, 95);
    return;
  }
  // Amplification bound on client-controlled geometry: every CDC spec in
  // the cluster cuts chunks well above this floor, so a recipe declaring
  // more entries than <logical / floor> is hostile or corrupt — without
  // the bound a 64 MB recipe of 1-byte chunks would pin and materialize
  // millions of chunk-store files for a few MB of payload.
  constexpr int64_t kMinNegotiatedChunk = 1024;
  if (logical < 0 || n_chunks <= 0 || n_chunks > (1 << 22) ||
      n_chunks > logical / kMinNegotiatedChunk + 1 ||
      c->fixed.size() != kPrefix + static_cast<size_t>(n_chunks) * 28) {
    Respond(c, 22);
    return;
  }
  auto s = std::make_unique<UploadSession>();
  s->recipe.logical_size = logical;
  s->recipe.chunks.reserve(static_cast<size_t>(n_chunks));
  int64_t covered = 0;
  const uint8_t* e = p + kPrefix;
  for (int64_t i = 0; i < n_chunks; ++i) {
    int64_t len = GetInt64BE(e + i * 28 + 20);
    // Same per-chunk cap as SYNC_CREATE_RECIPE: no declared entry may
    // make the phase-2 worker allocate unboundedly.
    if (len <= 0 || len > kMaxChunkPayload) {
      Respond(c, 22);
      return;
    }
    s->recipe.chunks.push_back({BytesToHex(e + i * 28, 20), len});
    covered += len;
  }
  if (covered != logical) {
    Respond(c, 22);
    return;
  }
  s->id = next_ingest_session_.fetch_add(1);
  s->spi = spi;
  s->ext = std::move(ext);
  s->crc32 = crc;
  s->cs = chunk_stores_[spi].get();
  // Probe + pin under ONE store-lock acquisition; from here the
  // session's destructor owns the unpin.
  s->needed = s->cs->PinAndMask(s->recipe);
  int64_t missing = 0;
  for (size_t i = 0; i < s->needed.size(); ++i) {
    if (s->needed[i] != 0) {
      ++missing;
      s->needed_bytes += s->recipe.chunks[i].length;
    }
  }
  s->deadline_s = time(nullptr) + cfg_.upload_session_timeout_s;
  c->ingest_chunks_total = n_chunks;
  c->ingest_chunks_missing = missing;
  std::string body(8, '\0');
  PutInt64BE(s->id, reinterpret_cast<uint8_t*>(body.data()));
  body += s->needed;
  {
    std::lock_guard<RankedMutex> lk(ingest_mu_);
    ingest_sessions_[s->id] = std::move(s);
  }
  negotiate.End();
  Respond(c, 0, body);
}

std::unique_ptr<StorageServer::UploadSession>
StorageServer::TakeIngestSession(int64_t id) {
  std::lock_guard<RankedMutex> lk(ingest_mu_);
  auto it = ingest_sessions_.find(id);
  if (it == ingest_sessions_.end()) return nullptr;
  auto s = std::move(it->second);
  ingest_sessions_.erase(it);
  return s;
}

void StorageServer::SweepIngestSessions() {
  // Destruction (unpin) happens OUTSIDE ingest_mu_: UnpinRecipe takes
  // the chunk-store mutex, and holding both here would order them
  // against every handler path for no benefit.
  std::vector<std::unique_ptr<UploadSession>> expired;
  int64_t now = time(nullptr);
  {
    std::lock_guard<RankedMutex> lk(ingest_mu_);
    for (auto it = ingest_sessions_.begin(); it != ingest_sessions_.end();) {
      if (it->second->deadline_s <= now) {
        expired.push_back(std::move(it->second));
        it = ingest_sessions_.erase(it);
      } else {
        ++it;
      }
    }
  }
  for (auto& s : expired) {
    FDFS_LOG_WARN("negotiated upload session %lld expired "
                  "(client vanished between RECIPE and CHUNKS): pins "
                  "released",
                  static_cast<long long>(s->id));
    if (ctr_ingest_fallbacks_ != nullptr)
      ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
    if (events_ != nullptr)
      events_->Record(EventSeverity::kWarn, "ingest.session_expired",
                      std::to_string(s->id),
                      "chunks=" + std::to_string(s->recipe.chunks.size()) +
                          " pinned_released=1");
  }
}

// UPLOAD_CHUNKS (133) prefix parse on the nio loop: resolve the
// session, validate the declared payload against what phase 1 computed,
// and open the tmp file the missing-chunk bytes stream into.
bool StorageServer::BeginUploadChunks(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t session_id = GetInt64BE(p);
  int64_t payload_len = GetInt64BE(p + 8);
  if (payload_len < 0 || c->pkg_len != 16 + payload_len) {
    RespondError(c, 22);
    return false;
  }
  int spi = -1;
  int64_t expect = -1;
  {
    std::lock_guard<RankedMutex> lk(ingest_mu_);
    auto it = ingest_sessions_.find(session_id);
    if (it != ingest_sessions_.end()) {
      spi = it->second->spi;
      expect = it->second->needed_bytes;
      // Restart the expiry clock now that the payload is arriving: the
      // phase-1 deadline covered the client's think time; without this
      // bump the sweep would expire a session whose client is actively
      // streaming a transfer longer than the timeout and force the
      // whole payload onto the plain path (~2x wire).
      it->second->deadline_s = time(nullptr) + cfg_.upload_session_timeout_s;
    }
  }
  if (spi < 0) {
    // Unknown or expired: the client falls back to a plain upload.  NOT
    // counted as a fallback — an expired session was already counted by
    // the sweep, and double-counting would skew the stuck-session
    // diagnosis OPERATIONS.md builds on this counter.
    RespondError(c, 2 /*ENOENT*/);
    return false;
  }
  if (payload_len != expect) {
    // Client/server disagree on what was missing: abort the session
    // (its pins included) rather than assemble a wrong file.
    TakeIngestSession(session_id).reset();
    if (ctr_ingest_fallbacks_ != nullptr)
      ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
    if (events_ != nullptr)
      events_->Record(EventSeverity::kWarn, "ingest.fallback",
                      std::to_string(session_id),
                      "phase=chunks reason=payload_mismatch declared=" +
                          std::to_string(payload_len) +
                          " expected=" + std::to_string(expect));
    RespondError(c, 22);
    return false;
  }
  c->ingest_session = session_id;
  c->store_path_index = spi;
  c->file_remaining = payload_len;
  c->tmp_path = store_.NewTmpPath(spi);
  c->file_fd = open(c->tmp_path.c_str(), O_CREAT | O_WRONLY | O_TRUNC, 0644);
  if (c->file_fd < 0) {
    RespondError(c, 5);
    return false;
  }
  c->state = ConnState::kRecvFile;
  return true;
}

// UPLOAD_CHUNKS completion (dio worker): assemble the logical stream
// once, segment by segment as this node would have cut it, in the buffer
// the thread keeps (AssembleCommit, ingest.h: shipped chunks admitted,
// present ones read by one ReadChunkSlices a segment, the CRC folded, the
// node's own fingerprint held to the recipe).  Then the file ID is minted
// from the content's CRC exactly like UPLOAD_FILE, the recipe stored and
// the fingerprint session committed.  All-or-nothing with ref rollback;
// any failure makes the client fall back to a plain upload.
void StorageServer::UploadChunksComplete(Conn* c) {
  close(c->file_fd);
  c->file_fd = -1;
  auto fail = [&](uint8_t status) {
    if (ctr_ingest_fallbacks_ != nullptr)
      ctr_ingest_fallbacks_->fetch_add(1, std::memory_order_relaxed);
    if (events_ != nullptr)
      events_->Record(EventSeverity::kWarn, "ingest.fallback",
                      std::to_string(c->ingest_session),
                      "phase=commit status=" + std::to_string(status) +
                          " peer=" + c->peer_ip);
    if (!c->tmp_path.empty()) {
      unlink(c->tmp_path.c_str());
      c->tmp_path.clear();
    }
    Respond(c, status);
  };
  // One commit per session: taking it here also closes the race with a
  // concurrent duplicate commit and the sweep timer.  The session (and
  // its pins) dies at scope exit — AFTER the refs below are taken, so
  // there is no unpinned-unreferenced window.
  auto s = TakeIngestSession(c->ingest_session);
  if (s == nullptr) {
    fail(2 /*ENOENT: expired mid-stream*/);
    return;
  }
  const Recipe& recipe = s->recipe;
  c->file_size = recipe.logical_size;  // upload-size histogram basis
  c->ingest_chunks_total = static_cast<int64_t>(recipe.chunks.size());
  int tmp_fd = open(c->tmp_path.c_str(), O_RDONLY);
  if (tmp_fd < 0) {
    fail(5);
    return;
  }
  // A plugin that indexes beside the chunk store never saw the client's
  // fingerprints: each assembled segment goes through it as an upload's
  // would, and its answer holds the client's recipe to this node's own
  // cut (a foreign cut is rolled back, the client uploads plain).  A
  // sidecar that cannot be reached fails open as everywhere.
  std::optional<FingerprintSession> fp;
  if (dedup_->IndexesBesideChunkStore()) fp.emplace(dedup_.get(), true);
  // The file ID's crc32 is identity metadata every consumer may check
  // (trunk slots already do): computed server-side over the logical
  // stream — shipped chunks from the wire payload, present chunks read
  // back from the store (local-disk cost, still far below re-shipping)
  // — never the client's claim.
  CommitAssembly a;
  uint8_t status = AssembleCommit(IngestFor(s->spi, dedup_.get(), &c->stages),
                                  recipe, s->needed, tmp_fd, s->id,
                                  fp ? &*fp : nullptr, &a);
  close(tmp_fd);
  unlink(c->tmp_path.c_str());
  c->tmp_path.clear();
  c->ingest_chunks_missing = a.missing;
  // The recipe's write is the chunk store's too (the access log's cswrite
  // column is present + verify + recipe).
  StageScope cs_write(&c->stages, Stage::kCsWrite);
  StageScope recipe_write(&c->stages, Stage::kRecipe);
  if (status == 0 && a.crc != s->crc32)
    FDFS_LOG_WARN("negotiated upload: client declared crc %u, content is %u "
                  "(ID minted from content)", s->crc32, a.crc);
  std::string id = status == 0 ? MintFileId(s->spi, recipe.logical_size,
                                            a.crc, s->ext, false)
                               : "";
  auto parts = id.empty() ? std::nullopt : DecodeFileId(id);
  std::optional<std::string> local =
      parts.has_value()
          ? LocalPath(store_.store_path(s->spi), parts->RemoteFilename())
          : std::nullopt;
  if (status == 0 && !local.has_value()) status = 22;
  std::string err;
  if (status == 0 && !s->cs->StoreRecipe(*local + ".rcp", recipe, &err)) {
    FDFS_LOG_ERROR("negotiated upload recipe write: %s", err.c_str());
    status = 5;
  }
  if (status != 0) {
    s->cs->UnrefAll(a.taken);
    fail(status);
    return;
  }
  recipe_write.End();
  cs_write.End();
  if (fp) {
    StageScope reindex(&c->stages, Stage::kReindex);
    if (a.fingerprinted)
      fp->Commit(cfg_.group_name + "/" + parts->RemoteFilename());
    else
      fp.reset();  // the abort, inside this interval
  }
  stats_.dedup_hits += a.hits;
  stats_.dedup_bytes_saved += a.saved;
  if (ingest_ctrs_.chunk_hits != nullptr) {
    ingest_ctrs_.chunk_hits->fetch_add(a.hits, std::memory_order_relaxed);
    ingest_ctrs_.chunk_misses->fetch_add(a.missing, std::memory_order_relaxed);
  }
  // Wire accounting: `saved` bytes never left the client — the whole
  // point of the negotiated path.
  if (ctr_ingest_recipe_uploads_ != nullptr) {
    ctr_ingest_recipe_uploads_->fetch_add(1, std::memory_order_relaxed);
    ctr_ingest_bytes_saved_wire_->fetch_add(a.saved,
                                            std::memory_order_relaxed);
    ctr_ingest_chunks_present_->fetch_add(a.hits, std::memory_order_relaxed);
    ctr_ingest_chunks_shipped_->fetch_add(a.missing,
                                          std::memory_order_relaxed);
  }
  AckCreated(c, parts->RemoteFilename());
}

// SYNC_CREATE_RECIPE (127): phase 2 of chunk-aware replication — take a
// reference on every chunk already present, write the shipped payloads
// for the missing ones, and store the recipe.  All-or-nothing: any
// failure rolls back taken refs and the sender falls back to the
// full-copy SYNC_CREATE_FILE.
void StorageServer::SyncRecipeComplete(Conn* c) {
  close(c->file_fd);
  c->file_fd = -1;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t logical = GetInt64BE(p + kGroupNameMaxLen + 8);
  int64_t n_chunks = GetInt64BE(p + kGroupNameMaxLen + 16);
  std::string local = ResolveLocal(cfg_.group_name, c->sync_remote);
  if (local.empty()) {
    unlink(c->tmp_path.c_str());
    Respond(c, 22);
    return;
  }
  // Idempotent replay: already materialized (flat or recipe) => done.
  struct stat st;
  if (stat(local.c_str(), &st) == 0 || RecipeExistsFor(local)) {
    unlink(c->tmp_path.c_str());
    binlog_.Append('c', c->sync_remote);
    Respond(c, 0);
    return;
  }
  ChunkStore* cs = chunk_stores_[c->store_path_index].get();
  const uint8_t* entries = p + 48 + name_len;
  // Validate every declared length BEFORE any side effects: an oversized
  // entry (corrupt or hostile) must be rejected outright, not allowed to
  // resize a multi-GB payload buffer on this dio worker; and no refs
  // should be taken for a replay that is doomed anyway.
  for (int64_t i = 0; i < n_chunks; ++i) {
    int64_t len = GetInt64BE(entries + i * 29 + 20);
    if (len <= 0 || len > kMaxChunkPayload) {
      FDFS_LOG_WARN("sync recipe %s: chunk %lld declares %lld bytes "
                    "(cap %lld): rejected", c->sync_remote.c_str(),
                    static_cast<long long>(i), static_cast<long long>(len),
                    static_cast<long long>(kMaxChunkPayload));
      unlink(c->tmp_path.c_str());
      c->tmp_path.clear();
      Respond(c, 22);
      return;
    }
  }
  int tmp_fd = open(c->tmp_path.c_str(), O_RDONLY);
  if (tmp_fd < 0) {
    unlink(c->tmp_path.c_str());
    Respond(c, 5);
    return;
  }
  Recipe recipe;
  recipe.logical_size = logical;
  int64_t saved = 0, hits = 0, covered = 0, tmp_off = 0;
  bool ok = true;
  for (int64_t i = 0; ok && i < n_chunks; ++i) {
    const uint8_t* e = entries + i * 29;
    // the length is validated above: (0, cap]
    const RecipeEntry entry{BytesToHex(e, 20), GetInt64BE(e + 20)};
    covered += entry.length;
    if (e[28] != 0) {  // shipped
      // Failing the replay makes the sender fall back to the full-copy
      // SYNC_CREATE_FILE.
      char* payload = KeptSegment(entry.length);
      ok = PreadFull(tmp_fd, payload, entry.length, tmp_off);
      tmp_off += entry.length;
      std::string err;
      const Admission a = ok ? AdmitChunk(cs, entry, payload, &recipe, &err)
                             : Admission::kStoreError;
      if (a == Admission::kNotItsDigest) {
        FDFS_LOG_WARN("sync recipe %s: chunk %s failed digest check",
                      c->sync_remote.c_str(), entry.digest_hex.c_str());
        if (ctr_sync_digest_mismatch_ != nullptr)
          ctr_sync_digest_mismatch_->fetch_add(1, std::memory_order_relaxed);
      }
      ok = a == Admission::kAdmitted;
    } else if (cs->RefOne(entry.digest_hex)) {
      saved += entry.length;
      ++hits;
      recipe.chunks.push_back(entry);
    } else {
      // The chunk vanished between query and create (concurrent
      // delete): report it and let the sender fall back to full copy.
      ok = false;
    }
  }
  ReleaseKeptSegment();
  close(tmp_fd);
  unlink(c->tmp_path.c_str());
  c->tmp_path.clear();
  std::string err;
  if (!ok || covered != logical ||
      !cs->StoreRecipe(local + ".rcp", recipe, &err)) {
    cs->UnrefAll(recipe);  // roll back what this replay referenced
    Respond(c, ok && covered != logical ? 22 : 5);
    return;
  }
  stats_.dedup_hits += hits;
  stats_.dedup_bytes_saved += saved;
  // Wire accounting: `saved` bytes were ref'd locally instead of shipped
  // by the replication sender — the chunk-aware protocol's whole point.
  if (ctr_sync_bytes_saved_wire_ != nullptr)
    ctr_sync_bytes_saved_wire_->fetch_add(saved, std::memory_order_relaxed);
  binlog_.Append('c', c->sync_remote);
  Respond(c, 0);
}

// -- handlers -------------------------------------------------------------

bool StorageServer::BeginUpload(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int spi = p[0];
  int64_t size = GetInt64BE(p + 1);
  c->ext = ExtFromField(p + 9);
  if (size < 0 || c->pkg_len != 15 + size) {
    RespondError(c, 22);
    return false;
  }
  if (spi == 0xFF) {
    spi = store_.PickStorePath();
  } else if (spi >= store_.store_path_count()) {
    RespondError(c, 22);
    return false;
  }
  c->store_path_index = spi;
  c->file_size = size;
  c->file_remaining = size;
  c->crc32 = 0;
  // The whole-file digest is read by Judge / Commit in FinishUpload,
  // which an appender never reaches and a chunk-eligible upload only
  // when its chunked store failed (FinishUpload then takes the digest
  // from the tmp file): the receive stage hashes the rest.
  bool appender =
      static_cast<StorageCmd>(c->cmd) == StorageCmd::kUploadAppenderFile;
  c->hashing = dedup_ != nullptr && !appender && !ChunkEligible(size);
  if (c->hashing) c->sha1 = Sha1Stream();
  c->tmp_path = store_.NewTmpPath(spi);
  c->file_fd = open(c->tmp_path.c_str(), O_CREAT | O_WRONLY | O_TRUNC, 0644);
  if (c->file_fd < 0) {
    FDFS_LOG_ERROR("open %s: %s", c->tmp_path.c_str(), strerror(errno));
    RespondError(c, 5);
    return false;
  }
  return true;
}

std::string StorageServer::MintFileId(int spi, int64_t size, uint32_t crc,
                                      const std::string& ext, bool appender,
                                      const TrunkLocation* trunk_loc) {
  EncodeFileIdArgs a;
  a.group = cfg_.group_name;
  a.store_path_index = spi;
  a.source_ip = PackIp(MyIp());
  a.create_timestamp = static_cast<uint32_t>(time(nullptr));
  a.file_size = static_cast<uint64_t>(size);
  a.crc32 = crc;
  a.ext = ext;
  a.uniquifier = store_.NextUniquifier();
  a.appender = appender;
  a.trunk = trunk_loc != nullptr;
  a.trunk_loc = trunk_loc;
  auto id = EncodeFileId(a);
  return id.has_value() ? *id : "";
}

// -- trunk integration ----------------------------------------------------

void StorageServer::RefreshClusterParams() {
  if (reporter_ == nullptr) return;
  // Runs on the main-loop timer; every nio/dio thread reads this state
  // (TrunkEligible/TrunkAlloc/...), so the whole transition is one
  // critical section.  The allocator pointer is swapped, never mutated
  // live — handlers that copied the shared_ptr finish on the old pool.
  std::lock_guard<RankedMutex> lk(trunk_mu_);
  auto params = reporter_->cluster_params();
  auto get = [&params](const char* key, int64_t dflt) {
    auto it = params.find(key);
    return it == params.end() ? dflt : atoll(it->second.c_str());
  };
  trunk_enabled_ = get("use_trunk_file", 0) != 0;
  slot_min_size_ = get("slot_min_size", slot_min_size_);
  slot_max_size_ = get("slot_max_size", slot_max_size_);
  trunk_file_size_ = get("trunk_file_size", trunk_file_size_);
  // Migrator pacing is a cluster param (tracker.conf:
  // rebalance_bandwidth_mb_s) so every member of a draining group
  // drains at the operator's one configured pace.
  if (rebalance_ != nullptr)
    rebalance_->set_bandwidth_mb_s(
        static_cast<int>(get("rebalance_bandwidth_mb_s", 8)));
  auto [tip, tport] = reporter_->trunk_server();
  trunk_ip_ = tip;
  trunk_port_ = tport;
  trunk_epoch_ = reporter_->trunk_epoch();
  // Slot alloc_size fields are uint32: a trunk_file_size >= 4GiB would
  // silently truncate the initial whole-file free block and corrupt the
  // allocator's view.  Refuse and disable trunk rather than corrupt
  // (latched log: this refires every param tick).
  if (trunk_enabled_ && trunk_file_size_ >= (4LL << 30)) {
    if (!trunk_size_err_logged_) {
      FDFS_LOG_ERROR("trunk_file_size %lld >= 4GiB unsupported: trunk "
                     "disabled", static_cast<long long>(trunk_file_size_));
      trunk_size_err_logged_ = true;
    }
    trunk_enabled_ = false;
  }
  bool am_trunk = trunk_enabled_ && !trunk_ip_.empty() &&
                  trunk_ip_ == MyIp() && trunk_port_ == cfg_.port;
  // A zeroed trailer means "unknown" (e.g. the reporting tracker briefly
  // cannot reach its leader), not "role lost": hold the current role
  // rather than flapping, which would void slots handed out but not yet
  // written.  A genuine move always names a different server.
  if (trunk_enabled_ && trunk_ip_.empty()) am_trunk = is_trunk_server_;
  // Any tick without the role cancels an armed-but-unexpired grace:
  // otherwise a role flap during the grace leaves a stale (expired)
  // deadline that would skip the grace entirely on the next regain.
  if (!am_trunk) trunk_regain_not_before_ = 0;
  if (am_trunk && !is_trunk_server_) {
    if (held_trunk_role_before_) {
      // REGAINING the role: slots allocated by the interim trunk server
      // may still be replicating here; a rescan now would list them free
      // and hand them out again (silent data loss).  Wait out a grace
      // period first, then rebuild the pool from a fresh disk scan.
      // (Replication lag beyond the grace is a residual risk; the
      // complete fix is an allocation epoch checked in the trunk RPC.)
      if (trunk_regain_not_before_ == 0) {
        trunk_regain_not_before_ = time(nullptr) + kTrunkRegainGraceS;
        FDFS_LOG_WARN("trunk role regained: holding %d s for in-flight "
                      "interim allocations before rescan",
                      kTrunkRegainGraceS);
      }
      if (time(nullptr) < trunk_regain_not_before_) {
        is_trunk_server_ = false;  // serve flat-file fallback meanwhile
        return;
      }
    }
    trunk_alloc_.reset();  // always rescan on a false->true transition
  }
  if (am_trunk && trunk_alloc_ == nullptr) {
    auto alloc = std::make_shared<TrunkAllocator>();
    std::string err;
    if (alloc->Init(store_.store_path(0), trunk_file_size_, &err)) {
      trunk_alloc_ = std::move(alloc);
      held_trunk_role_before_ = true;
      trunk_regain_not_before_ = 0;
      FDFS_LOG_INFO("this server is now the trunk server (%d trunk files, "
                    "%lld free bytes)",
                    trunk_alloc_->trunk_file_count(),
                    static_cast<long long>(trunk_alloc_->free_bytes()));
    } else {
      FDFS_LOG_ERROR("trunk allocator init failed: %s", err.c_str());
      am_trunk = false;
    }
  } else if (!am_trunk && is_trunk_server_) {
    trunk_alloc_.reset();  // role genuinely moved: the pool goes stale the
                           // moment the new trunk server starts allocating
    trunk_regain_not_before_ = 0;
  }
  is_trunk_server_ = am_trunk;
}

bool StorageServer::TrunkEligible(int64_t size) const {
  std::lock_guard<RankedMutex> lk(trunk_mu_);
  return trunk_enabled_ && size >= slot_min_size_ && size < slot_max_size_ &&
         (is_trunk_server_ || trunk_port_ > 0);
}

// Trunk RPC timeout: these calls run synchronously on the nio loop (as
// upstream's do on its service threads), so a dead trunk server stalls
// this event loop for at most this long before the upload falls back to a
// flat file.  The beat trailer clears a dead trunk server within ~1
// heartbeat, so the stall is one-shot, but an async alloc path would
// remove it entirely.
constexpr int kTrunkRpcTimeoutMs = 1000;

std::optional<TrunkLocation> StorageServer::TrunkAlloc(int64_t payload_size) {
  std::shared_ptr<TrunkAllocator> alloc;
  std::string ip;
  int port = 0;
  int64_t epoch = 0;
  {
    std::lock_guard<RankedMutex> lk(trunk_mu_);
    if (is_trunk_server_) alloc = trunk_alloc_;
    ip = trunk_ip_;
    port = trunk_port_;
    epoch = trunk_epoch_;
  }
  if (alloc != nullptr) return alloc->Alloc(payload_size);
  if (port > 0)
    return TrunkAllocRpc(ip, port, cfg_.group_name, payload_size, epoch,
                         kTrunkRpcTimeoutMs);
  return std::nullopt;
}

void StorageServer::TrunkFree(const TrunkLocation& loc) {
  std::shared_ptr<TrunkAllocator> alloc;
  std::string trunk_ip;
  int trunk_port = 0;
  int64_t epoch = 0;
  {
    std::lock_guard<RankedMutex> lk(trunk_mu_);
    if (is_trunk_server_) alloc = trunk_alloc_;
    trunk_ip = trunk_ip_;
    trunk_port = trunk_port_;
    epoch = trunk_epoch_;
  }
  if (alloc != nullptr) {
    alloc->Free(loc);
    return;
  }
  // Not the trunk server: free OUR copy of the slot on disk, then return
  // it to the group allocator.  (The RPC frees the trunk server's copy;
  // remaining replicas free theirs via the 'd' binlog replay.)
  MarkSlotFree(store_.store_path(0), loc);
  if (trunk_port > 0) {
    if (!TrunkFreeRpc(trunk_ip, trunk_port, cfg_.group_name, loc, epoch,
                      kTrunkRpcTimeoutMs))
      FDFS_LOG_WARN("trunk free RPC failed (id=%u off=%u): slot leaked until "
                    "the free-block checker reclaims it",
                    loc.trunk_id, loc.offset);
  }
}

std::string StorageServer::TrunkStoreUpload(Conn* c) {
  auto loc = TrunkAlloc(c->file_size);
  if (!loc.has_value()) return "";
  std::string payload;
  if (!ReadWholeFile(c->tmp_path, &payload) ||
      static_cast<int64_t>(payload.size()) != c->file_size) {
    TrunkFree(*loc);
    return "";
  }
  std::string err;
  if (!WriteSlotPayload(store_.store_path(0), *loc, payload, c->crc32,
                        &err)) {
    FDFS_LOG_ERROR("trunk slot write: %s", err.c_str());
    TrunkFree(*loc);
    return "";
  }
  // Trunk files always live under store path 0 (see trunk.h divergences).
  std::string id = MintFileId(0, c->file_size, c->crc32, c->ext,
                              /*appender=*/false, &*loc);
  if (id.empty()) {
    TrunkFree(*loc);
    return "";
  }
  bool am_trunk;
  std::string tip;
  int tport;
  int64_t tepoch;
  {
    std::lock_guard<RankedMutex> lk(trunk_mu_);
    am_trunk = is_trunk_server_;
    tip = trunk_ip_;
    tport = trunk_port_;
    tepoch = trunk_epoch_;
  }
  if (!am_trunk) TrunkConfirmRpc(tip, tport, cfg_.group_name, *loc, tepoch,
                                 kTrunkRpcTimeoutMs);
  return id;
}

void StorageServer::HandleTrunkRpc(Conn* c) {
  auto cmd = static_cast<StorageCmd>(c->cmd);
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < 16 + 8 ||
      GroupFromField(p) != cfg_.group_name) {
    Respond(c, 22);
    return;
  }
  std::shared_ptr<TrunkAllocator> alloc;
  int64_t slot_max;
  int64_t my_epoch;
  {
    std::lock_guard<RankedMutex> lk(trunk_mu_);
    if (is_trunk_server_) alloc = trunk_alloc_;
    slot_max = slot_max_size_;
    my_epoch = trunk_epoch_;
  }
  if (alloc == nullptr) {
    Respond(c, 1 /*EPERM: not the trunk server*/);
    return;
  }
  // Epoch fencing: the RPC's trailing 8 bytes carry the caller's trunk
  // epoch (tracker-bumped on every role change).  A mismatch means a
  // stale trunk server serving after the role moved, or a stale client
  // — either way refuse (the caller falls back to a flat file) instead
  // of allocating a slot another server also thinks it owns.
  bool is_alloc = cmd == StorageCmd::kTrunkAllocSpace;
  size_t base = is_alloc ? 16u + 8u : 16u + 12u;
  if (c->fixed.size() < base + 8) {
    // The epoch is MANDATORY — an optional fence is no fence.
    Respond(c, 22);
    return;
  }
  int64_t caller_epoch = GetInt64BE(
      reinterpret_cast<const uint8_t*>(c->fixed.data()) + base);
  if (caller_epoch != my_epoch) {
    FDFS_LOG_WARN("trunk RPC epoch mismatch (caller %lld, mine %lld): "
                  "refusing", static_cast<long long>(caller_epoch),
                  static_cast<long long>(my_epoch));
    Respond(c, 16 /*EBUSY: stale role*/);
    return;
  }
  if (cmd == StorageCmd::kTrunkAllocSpace) {
    int64_t size = GetInt64BE(p + 16);
    if (size <= 0 || size >= slot_max) {
      Respond(c, 22);
      return;
    }
    auto loc = alloc->Alloc(size);
    if (!loc.has_value()) {
      Respond(c, 28 /*ENOSPC*/);
      return;
    }
    std::string out(12, '\0');
    uint8_t* q = reinterpret_cast<uint8_t*>(out.data());
    PutInt32BE(loc->trunk_id, q);
    PutInt32BE(loc->offset, q + 4);
    PutInt32BE(loc->alloc_size, q + 8);
    Respond(c, 0, out);
    return;
  }
  if (c->fixed.size() < 16 + 12) {
    Respond(c, 22);
    return;
  }
  TrunkLocation loc;
  loc.trunk_id = GetInt32BE(p + 16);
  loc.offset = GetInt32BE(p + 20);
  loc.alloc_size = GetInt32BE(p + 24);
  if (cmd == StorageCmd::kTrunkAllocConfirm) {
    // Allocation was durable at alloc time (see trunk.h divergences).
    Respond(c, 0);
    return;
  }
  Respond(c, alloc->Free(loc) ? 0 : 22);
}

bool StorageStats::SaveToFile(const std::string& path) const {
  // File keeps its historical 20-line shape (19 persisted counters + one
  // spare) so stat files from earlier builds load unchanged.
  int64_t v[20] = {0};
  Snapshot(v);
  std::string tmp = path + ".tmp";
  FILE* f = fopen(tmp.c_str(), "w");
  if (f == nullptr) return false;
  for (int i = 0; i < 20; ++i)
    fprintf(f, "%lld\n", static_cast<long long>(v[i]));
  fclose(f);
  return rename(tmp.c_str(), path.c_str()) == 0;
}

bool StorageStats::LoadFromFile(const std::string& path) {
  FILE* f = fopen(path.c_str(), "r");
  if (f == nullptr) return false;
  long long v[20] = {0};
  for (int i = 0; i < 20; ++i)
    if (fscanf(f, "%lld", &v[i]) != 1) break;
  fclose(f);
  total_upload = v[0]; success_upload = v[1];
  total_download = v[2]; success_download = v[3];
  total_delete = v[4]; success_delete = v[5];
  total_append = v[6]; success_append = v[7];
  total_set_meta = v[8]; success_set_meta = v[9];
  total_get_meta = v[10]; success_get_meta = v[11];
  total_query = v[12]; success_query = v[13];
  bytes_uploaded = v[14]; bytes_downloaded = v[15];
  dedup_hits = v[16]; dedup_bytes_saved = v[17];
  last_source_update = v[18];
  return true;
}

bool StorageServer::RemoteExists(const std::string& group,
                                 const std::string& remote,
                                 const std::string& local) {
  auto parts = DecodeFileId(group + "/" + remote);
  if (parts.has_value() && parts->trunk_loc.has_value()) {
    std::string tp =
        TrunkFilePath(store_.store_path(0), parts->trunk_loc->trunk_id);
    int fd = open(tp.c_str(), O_RDONLY);
    if (fd < 0) return false;
    auto h = ReadSlotHeader(fd, parts->trunk_loc->offset);
    close(fd);
    return h.has_value() && h->type == kTrunkSlotData &&
           h->alloc_size == parts->trunk_loc->alloc_size &&
           h->file_size == parts->file_size && h->crc32 == parts->crc32;
  }
  struct stat st;
  return stat(local.c_str(), &st) == 0 ||
         RecipeExistsFor(local);  // chunk recipe (flat or slab record)
}

// FETCH_ONE_PATH_BINLOG (26): binlog records whose file lives on the
// requested store path, as raw lines — the feed a recovering peer replays
// to re-download its wiped disk (storage_disk_recovery.c).  Paged: the
// optional request offset indexes the FILTERED stream and a short (or
// empty) page signals the end, so a multi-year binlog never has to fit
// in one response.
void StorageServer::HandleFetchOnePathBinlog(Conn* c) {
  constexpr int64_t kPageBytes = 8 << 20;
  if (c->fixed.size() < 17) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (GroupFromField(p) != cfg_.group_name) {
    Respond(c, 22);
    return;
  }
  int spi = static_cast<uint8_t>(c->fixed[16]);
  if (spi >= store_.store_path_count()) {
    Respond(c, 22);
    return;
  }
  int64_t offset = c->fixed.size() >= 25 ? GetInt64BE(p + 17) : 0;
  if (offset < 0) {
    Respond(c, 22);
    return;
  }
  Respond(c, 0, CollectOnePathBinlog(cfg_.base_path + "/data/sync", spi,
                                     offset, kPageBytes));
}

void StorageServer::HandleTrunkDownload(Conn* c, const FileIdParts& parts,
                                        int64_t offset, int64_t count) {
  const TrunkLocation& loc = *parts.trunk_loc;
  std::string path = TrunkFilePath(store_.store_path(0), loc.trunk_id);
  int fd = open(path.c_str(), O_RDONLY);
  if (fd < 0) {
    Respond(c, 2);
    return;
  }
  auto h = ReadSlotHeader(fd, loc.offset);
  // Full identity check (size AND crc): a reused slot can coincide in
  // size with the deleted file; serving the new occupant's bytes under
  // the old ID would be cross-file content disclosure.
  if (!h.has_value() || h->type != kTrunkSlotData ||
      h->alloc_size != loc.alloc_size || h->file_size != parts.file_size ||
      h->crc32 != parts.crc32) {
    close(fd);
    Respond(c, 2);  // slot reused or freed: the file is gone
    return;
  }
  int64_t size = h->file_size;
  if (offset > size) {
    close(fd);
    Respond(c, 22);
    return;
  }
  int64_t avail = size - offset;
  if (count == 0 || count > avail) count = avail;
  stats_.success_download++;
  RespondFile(c, 0, fd, loc.offset + kTrunkHeaderSize + offset, count);
}

void StorageServer::FinishUpload(Conn* c) {
  close(c->file_fd);
  c->file_fd = -1;
  bool appender =
      static_cast<StorageCmd>(c->cmd) == StorageCmd::kUploadAppenderFile;

  std::string digest;
  if (c->hashing) digest = c->sha1.Final().Hex();

  // Chunk-level dedup (north star): large uploads are CDC-chunked, the
  // chunks fingerprinted (on the TPU in sidecar mode), and only bytes the
  // chunk store has never seen are written — the file itself becomes a
  // small recipe.  Appenders stay flat (mutable).  Failure of any kind
  // falls through to the classic flat store.
  if (!appender && ChunkEligible(c->file_size)) {
    std::string id = MintFileId(c->store_path_index, c->file_size, c->crc32,
                                c->ext, false);
    std::optional<FileIdParts> parts;
    if (!id.empty()) parts = DecodeFileId(id);
    if (parts.has_value()) {
      std::string local = LocalPath(store_.store_path(c->store_path_index),
                                    parts->RemoteFilename())
                              .value();
      // No EnsureParentDirs here: a slab-resident recipe needs no
      // fan-out directory (StoreRecipe creates the chain only for the
      // flat sidecar; the flat-store fallback below makes its own).
      int64_t saved = 0, hits = 0;
      if (StoreChunked(IngestFor(c->store_path_index, dedup_.get(),
                                 &c->stages),
                       c->tmp_path, c->file_size, local + ".rcp",
                       cfg_.group_name + "/" + parts->RemoteFilename(), &saved,
                       &hits)) {
        unlink(c->tmp_path.c_str());
        c->tmp_path.clear();
        stats_.dedup_hits += hits;
        stats_.dedup_bytes_saved += saved;
        AckCreated(c, parts->RemoteFilename());
        return;
      }
    }
  }

  // Dedup verdict (plugin boundary; appender files are mutable => exempt).
  if (dedup_ != nullptr && !appender) {
    if (!c->hashing) {
      // A chunk-eligible upload whose chunked store failed (a dead
      // sidecar, refused widths): the digest the receive stage left out.
      auto late = Sha1OfFile(c->tmp_path, c->file_size,
                             cfg_.dedup_segment_bytes);
      if (!late.has_value()) {
        FDFS_LOG_ERROR("re-read of %s failed", c->tmp_path.c_str());
        unlink(c->tmp_path.c_str());
        c->tmp_path.clear();
        Respond(c, 5);
        return;
      }
      digest = late->Hex();
      ctr_fallback_rehash_->fetch_add(1, std::memory_order_relaxed);
    }
    auto verdict = dedup_->Judge(digest, c->file_size);
    if (verdict.duplicate) {
      auto dup = DecodeFileId(verdict.dup_of);
      if (dup.has_value() && dup->group == cfg_.group_name &&
          dup->store_path_index < store_.store_path_count()) {
        int spi = dup->store_path_index;
        std::string id = MintFileId(spi, c->file_size, c->crc32, c->ext, false);
        auto parts = DecodeFileId(id);
        std::string new_local =
            LocalPath(store_.store_path(spi), parts->RemoteFilename()).value();
        std::string dup_local =
            LocalPath(store_.store_path(spi), dup->RemoteFilename()).value();
        StoreManager::EnsureParentDirs(new_local);
        if (link(dup_local.c_str(), new_local.c_str()) == 0) {
          unlink(c->tmp_path.c_str());
          c->tmp_path.clear();
          stats_.dedup_hits++;
          stats_.dedup_bytes_saved += c->file_size;
          stats_.success_upload++;
          stats_.last_source_update = time(nullptr);
          binlog_.Append(kBinlogOpLink, parts->RemoteFilename(),
                         dup->RemoteFilename());
          NoteTracedMutation(c, parts->RemoteFilename());
          NoteHeat(c, HeatOp::kUpload,
                   cfg_.group_name + "/" + parts->RemoteFilename());
          Respond(c, 0, PackGroupField(cfg_.group_name) + parts->RemoteFilename());
          return;
        }
        // Stale mapping (canonical copy deleted): fall through to a normal
        // store and let Commit repoint the digest.
        dedup_->Forget(verdict.dup_of);
      }
    }
  }

  // Small-file packing (SURVEY §2.3): eligible uploads go into a trunk
  // slot instead of their own inode; failure falls back to a flat file.
  if (!appender && TrunkEligible(c->file_size)) {
    std::string tid = TrunkStoreUpload(c);
    if (!tid.empty()) {
      unlink(c->tmp_path.c_str());
      c->tmp_path.clear();
      auto tparts = DecodeFileId(tid);
      if (dedup_ != nullptr) dedup_->Commit(digest, tid);
      binlog_.Append(kBinlogOpCreate, tparts->RemoteFilename());
      NoteTracedMutation(c, tparts->RemoteFilename());
      stats_.success_upload++;
      stats_.last_source_update = time(nullptr);
      NoteHeat(c, HeatOp::kUpload,
               cfg_.group_name + "/" + tparts->RemoteFilename());
      Respond(c, 0, PackGroupField(cfg_.group_name) + tparts->RemoteFilename());
      return;
    }
  }

  std::string id = MintFileId(c->store_path_index, c->file_size, c->crc32,
                              c->ext, appender);
  if (id.empty()) {
    unlink(c->tmp_path.c_str());
    Respond(c, 22);
    return;
  }
  auto parts = DecodeFileId(id);
  std::string local = LocalPath(store_.store_path(c->store_path_index),
                                parts->RemoteFilename())
                          .value();
  StoreManager::EnsureParentDirs(local);
  if (rename(c->tmp_path.c_str(), local.c_str()) != 0) {
    FDFS_LOG_ERROR("rename %s -> %s: %s", c->tmp_path.c_str(), local.c_str(),
                   strerror(errno));
    unlink(c->tmp_path.c_str());
    Respond(c, 5);
    return;
  }
  c->tmp_path.clear();
  if (dedup_ != nullptr && !appender) dedup_->Commit(digest, id);
  AckCreated(c, parts->RemoteFilename());
}

void StorageServer::AckCreated(Conn* c, const std::string& remote) {
  {
    StageScope binlog(&c->stages, Stage::kBinlog);
    binlog_.Append(kBinlogOpCreate, remote);
  }
  NoteTracedMutation(c, remote);
  stats_.success_upload++;
  stats_.last_source_update = time(nullptr);
  NoteHeat(c, HeatOp::kUpload, cfg_.group_name + "/" + remote);
  Respond(c, 0, PackGroupField(cfg_.group_name) + remote);
}

std::string StorageServer::ResolveLocal(const std::string& group,
                                        const std::string& remote) const {
  if (group != cfg_.group_name) return "";
  int spi = 0;
  if (remote.size() < 3 || sscanf(remote.c_str(), "M%02X/", &spi) != 1)
    return "";
  if (spi >= store_.store_path_count()) return "";
  auto lp = LocalPath(store_.store_path(spi), remote);
  return lp.has_value() ? *lp : "";
}

// -- chunk-level dedup (north star) ---------------------------------------

bool StorageServer::ChunkEligible(int64_t size) const {
  return dedup_ != nullptr && cfg_.dedup_chunk_threshold > 0 &&
         size >= cfg_.dedup_chunk_threshold && !chunk_stores_.empty();
}

ChunkStore* StorageServer::StoreForLocal(const std::string& local) const {
  for (int i = 0; i < store_.store_path_count() &&
                  i < static_cast<int>(chunk_stores_.size()); ++i) {
    const std::string& sp = store_.store_path(i);
    if (local.compare(0, sp.size(), sp) == 0) return chunk_stores_[i].get();
  }
  return nullptr;
}

std::optional<Recipe> StorageServer::LoadRecipeFor(
    const std::string& local) const {
  ChunkStore* cs = StoreForLocal(local);
  return cs != nullptr ? cs->LoadRecipe(local + ".rcp")
                       : ReadRecipeFile(local + ".rcp");
}

bool StorageServer::RecipeExistsFor(const std::string& local) const {
  ChunkStore* cs = StoreForLocal(local);
  if (cs != nullptr) return cs->HasRecipe(local + ".rcp");
  struct stat st;
  return stat((local + ".rcp").c_str(), &st) == 0;
}

IngestTarget StorageServer::IngestFor(int spi, DedupPlugin* plugin,
                                      StageTrace* stages) const {
  // ec_k > 0 and ec_demote_age_s = 0: erasure coding on write.
  return {chunk_stores_[spi].get(), plugin, cfg_.dedup_segment_bytes,
          cfg_.ec_k > 0 && cfg_.ec_demote_age_s == 0, stages, ingest_ctrs_};
}

int64_t StorageServer::LogicalSize(const std::string& local) const {
  struct stat st;
  if (stat(local.c_str(), &st) == 0) return st.st_size;
  auto r = LoadRecipeFor(local);
  return r.has_value() ? r->logical_size : -1;
}

int StorageServer::OpenLogical(const std::string& local, int64_t* size) {
  int fd = open(local.c_str(), O_RDONLY);
  if (fd >= 0) {
    struct stat st;
    fstat(fd, &st);
    *size = st.st_size;
    return fd;
  }
  auto r = LoadRecipeFor(local);
  if (!r.has_value()) return -1;
  ChunkStore* cs = StoreForLocal(local);
  if (cs == nullptr) return -1;
  // Materialize into an unlinked temp file: downstream sendfile paths
  // (downloads, sync replication) keep working unchanged, and the bytes
  // are reclaimed automatically on close.  The temp lives under the
  // store path's always-present tmp/ dir, NOT next to `local` — a
  // slab-resident recipe's fan-out directory may never have existed
  // (lazy dirs are the slab layout's inode win).
  std::string tmp;
  for (int i = 0; i < store_.store_path_count(); ++i) {
    const std::string& sp = store_.store_path(i);
    if (local.compare(0, sp.size(), sp) == 0) {
      tmp = store_.NewTmpPath(i);
      break;
    }
  }
  if (tmp.empty()) tmp = local + ".assm." + std::to_string(getpid());
  fd = open(tmp.c_str(), O_CREAT | O_RDWR | O_TRUNC, 0600);
  if (fd < 0) return -1;
  unlink(tmp.c_str());
  // kIoBufSize segments: the kept buffer of a replication thread stays
  // small.
  if (!WalkRecipe(cs, *r, kIoBufSize, [fd](int64_t, char* seg, int64_t len) {
        for (int64_t off = 0; off < len;) {
          const ssize_t w = write(fd, seg + off, static_cast<size_t>(len - off));
          if (w <= 0) return false;
          off += w;
        }
        return true;
      })) {
    FDFS_LOG_ERROR("cannot assemble %s from its chunks", local.c_str());
    close(fd);
    return -1;
  }
  *size = r->logical_size;
  lseek(fd, 0, SEEK_SET);
  return fd;
}

int StorageServer::RemoveLogical(const std::string& local,
                                 const std::string& file_ref) {
  // Delete the recipe sidecar WITH the file id and account its bytes to
  // the integrity engine (scrub.bytes_reclaimed / recipes_reclaimed):
  // the recipe — flat .rcp inode or slab record — is real disk the
  // delete reclaims, same as the chunks GC frees later (slab records go
  // dead now and the compactor returns the bytes).
  auto drop_recipe = [this, &local, &file_ref](const std::string& rcp) {
    ChunkStore* cs = StoreForLocal(local);
    auto r = cs != nullptr ? cs->LoadRecipe(rcp) : ReadRecipeFile(rcp);
    if (!r.has_value()) return 2;
    int64_t rcp_bytes = 0;
    if (cs != nullptr) {
      if (!cs->RemoveRecipe(rcp, &rcp_bytes)) return 5;
    } else {
      struct stat st;
      rcp_bytes = stat(rcp.c_str(), &st) == 0 ? st.st_size : 0;
      if (unlink(rcp.c_str()) != 0 && errno != ENOENT) return 5;
    }
    if (cs != nullptr) cs->UnrefAll(*r);
    if (dedup_ != nullptr) dedup_->ForgetChunked(file_ref);
    if (scrub_ != nullptr) scrub_->NoteRecipeReclaimed(rcp_bytes);
    return 0;
  };
  std::string rcp = local + ".rcp";
  if (unlink(local.c_str()) == 0) {
    // Flat inode gone; also clear any stale recipe sidecar left under
    // the same name (belt-and-braces — the two should never coexist,
    // but a leaked recipe would hold chunk refs forever).
    if (RecipeExistsFor(local)) drop_recipe(rcp);
    return 0;
  }
  if (errno != ENOENT) return 5;
  return drop_recipe(rcp);
}

void StorageServer::HandleDownload(Conn* c) {
  stats_.total_download++;
  // body: 8B offset + 8B count + 16B group + remote_filename
  if (c->fixed.size() < 16 + 16 + 10) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t offset = GetInt64BE(p);
  int64_t count = GetInt64BE(p + 8);
  std::string group = GroupFromField(p + 16);
  std::string remote = c->fixed.substr(32);
  if (offset < 0 || count < 0) {
    Respond(c, 22);
    return;
  }
  // Heat: every download attempt (including failures — a hot missing
  // key is an operator signal too) counts against its file-id.
  NoteHeat(c, HeatOp::kDownload, group + "/" + remote);
  // Trunk files are served out of their slot, not an inode of their own.
  auto tparts = DecodeFileId(group + "/" + remote);
  if (tparts.has_value() && tparts->trunk_loc.has_value()) {
    if (group != cfg_.group_name) {
      Respond(c, 22);
      return;
    }
    HandleTrunkDownload(c, *tparts, offset, count);
    return;
  }
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  // Ranged request = explicit offset or byte count (the parallel
  // client splits one file into ranges; per-replica affinity makes the
  // read caches accumulate hits).  Counted once per request, with the
  // bytes actually served.
  bool ranged = offset != 0 || count != 0;
  auto note_ranged = [&](int64_t served) {
    if (ranged && ctr_download_ranged_requests_ != nullptr) {
      ctr_download_ranged_requests_->fetch_add(1, std::memory_order_relaxed);
      ctr_download_ranged_bytes_->fetch_add(served,
                                            std::memory_order_relaxed);
    }
  };
  int fd = open(local.c_str(), O_RDONLY);
  if (fd >= 0) {  // flat file: sendfile
    struct stat st;
    fstat(fd, &st);
    int64_t size = st.st_size;
    if (offset > size) {
      close(fd);
      Respond(c, 22);
      return;
    }
    int64_t avail = size - offset;
    if (count == 0 || count > avail) count = avail;
    stats_.success_download++;
    note_ranged(count);
    RespondFile(c, 0, fd, offset, count);
    return;
  }
  // Chunk recipe: stream chunk-by-chunk as the socket drains — never
  // materialize the logical file (a multi-GB download must not stall
  // this loop's other connections).
  ChunkStore* cs = StoreForLocal(local);
  if (cs == nullptr) {
    // No chunk store for this path (dedup off).  If a recipe exists the
    // file is REAL data from an earlier dedup_mode config — answer EIO
    // (retryable) so disk recovery never mistakes it for deleted; with
    // no recipe either, the file is simply gone: ENOENT, which recovery
    // treats as "deleted on the peer, skip".
    Respond(c, access((local + ".rcp").c_str(), F_OK) == 0 ? 5 : 2);
    return;
  }
  // Read + pin-per-chunk (verify under the stripe lock): a delete
  // between a plain read and a later pin could unlink chunks this
  // stream is about to send.  Ranged requests pin ONLY the overlapping
  // recipe slice — a 4-range parallel download of a many-thousand-chunk
  // file must not pay 4x full-recipe pin/unpin.
  int64_t skip = 0;
  auto r = cs->ReadRecipeAndPinRange(local + ".rcp", offset, count, &skip);
  if (!r.has_value()) {
    Respond(c, 2);
    return;
  }
  int64_t size = r->logical_size;
  if (offset > size) {
    cs->UnpinRecipe(*r);  // empty slice: no pins were taken
    Respond(c, 22);
    return;
  }
  int64_t avail = size - offset;
  if (count == 0 || count > avail) count = avail;
  auto rs = std::make_unique<RecipeStream>();
  rs->cs = cs;
  rs->remaining = count;
  rs->skip = skip;
  rs->recipe = std::move(*r);
  rs->pinned = true;  // pinned by ReadRecipeAndPin above
  stats_.success_download++;
  note_ranged(count);
  LogAccess(c, 0, count);
  c->out.resize(kHeaderSize);
  PutInt64BE(count, reinterpret_cast<uint8_t*>(c->out.data()));
  c->out[8] = static_cast<char>(StorageCmd::kResp);
  c->out[9] = 0;
  c->out_off = 0;
  c->rstream = std::move(rs);
  c->state = ConnState::kSend;
  if (!c->async_pending) WriteConn(c);
}

void StorageServer::HandleDelete(Conn* c) {
  // Chunk-recipe GC can unref thousands of chunks; run it off-loop on
  // the file's OWN store-path pool (cross-path deletes must not starve
  // another path's uploads).
  int spi = 0;
  if (c->fixed.size() >= 16 + 4)
    sscanf(c->fixed.c_str() + 16, "M%02X/", &spi);
  OffloadToDio(c, spi, [this, c] { DeleteWork(c); });
}

void StorageServer::DeleteWork(Conn* c) {
  bool replica = static_cast<StorageCmd>(c->cmd) == StorageCmd::kSyncDeleteFile;
  if (!replica) stats_.total_delete++;
  if (c->fixed.size() < 16 + 10) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  std::string group = GroupFromField(p);
  std::string remote = c->fixed.substr(16);
  auto tparts = DecodeFileId(group + "/" + remote);
  if (tparts.has_value() && tparts->trunk_loc.has_value()) {
    // Trunk delete: release the slot.  The header's identity facts must
    // match the deleting ID — an async 'd' replay arriving after the slot
    // was reused must NOT free the new occupant.
    if (group != cfg_.group_name) {
      Respond(c, 22);
      return;
    }
    std::string tpath =
        TrunkFilePath(store_.store_path(0), tparts->trunk_loc->trunk_id);
    int tfd = open(tpath.c_str(), O_RDONLY);
    std::optional<TrunkSlotHeader> h;
    if (tfd >= 0) {
      h = ReadSlotHeader(tfd, tparts->trunk_loc->offset);
      close(tfd);
    }
    bool live = h.has_value() && h->type == kTrunkSlotData &&
                h->alloc_size == tparts->trunk_loc->alloc_size &&
                h->file_size == tparts->file_size &&
                h->crc32 == tparts->crc32;
    std::string sidecar = ResolveLocal(group, remote);
    if (replica) {
      // Replay: free our local copy if this exact file still occupies the
      // slot; otherwise it is already gone (or reused) — both fine.
      if (live) MarkSlotFree(store_.store_path(0), *tparts->trunk_loc);
      if (!sidecar.empty()) unlink((sidecar + "-m").c_str());
      binlog_.Append('d', remote);
      Respond(c, 0);
      return;
    }
    if (!live) {
      Respond(c, 2);
      return;
    }
    TrunkFree(*tparts->trunk_loc);
    if (!sidecar.empty()) unlink((sidecar + "-m").c_str());
    if (dedup_ != nullptr) dedup_->Forget(group + "/" + remote);
    binlog_.Append(kBinlogOpDelete, remote);
    stats_.success_delete++;
    stats_.last_source_update = time(nullptr);
    Respond(c, 0);
    return;
  }
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  int rc = RemoveLogical(local, group + "/" + remote);
  if (rc != 0) {
    Respond(c, static_cast<uint8_t>(rc));
    return;
  }
  unlink((local + "-m").c_str());  // metadata sidecar, if any
  if (dedup_ != nullptr) dedup_->Forget(group + "/" + remote);
  binlog_.Append(replica ? 'd' : kBinlogOpDelete, remote);
  if (!replica) {
    stats_.success_delete++;
    stats_.last_source_update = time(nullptr);
  }
  Respond(c, 0);
}

void StorageServer::HandleNearDups(Conn* c) {
  // Operator near-dup query: "what is this file similar to?", answered
  // from the dedup engine's MinHash/LSH index.  Body mirrors
  // kQueryFileInfo (16B group + remote filename); response is ranked
  // text lines "<file_id> <score>".  The sidecar RPC blocks, so the
  // work leaves the nio loop.
  if (c->fixed.size() < 16 + 10) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  std::string group = GroupFromField(p);
  if (group != cfg_.group_name) {
    Respond(c, 22);
    return;
  }
  OffloadToDio(c, 0, [this, c] {
    std::string file_id = cfg_.group_name + "/" + c->fixed.substr(16);
    std::string out;
    bool no_data = false;
    if (dedup_ == nullptr || !dedup_->NearDups(file_id, &out, &no_data)) {
      Respond(c, 95);  // ENOTSUP: no near index in this dedup mode
      return;
    }
    Respond(c, no_data ? 61 : 0, out);  // ENODATA: file carries no signature
  });
}

void StorageServer::HandleQueryFileInfo(Conn* c) {
  stats_.total_query++;
  if (c->fixed.size() < 16 + 10) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  std::string group = GroupFromField(p);
  std::string remote = c->fixed.substr(16);
  // Identity facts come from the ID itself (no-metadata-database design).
  auto parts = DecodeFileId(group + "/" + remote);
  if (!parts.has_value()) {
    Respond(c, 22);
    return;
  }
  struct stat st;
  if (parts->trunk_loc.has_value()) {
    // Header-only stat: size + full identity check without touching the
    // payload bytes.
    std::string tp =
        TrunkFilePath(store_.store_path(0), parts->trunk_loc->trunk_id);
    int tfd = open(tp.c_str(), O_RDONLY);
    std::optional<TrunkSlotHeader> h;
    if (tfd >= 0) {
      h = ReadSlotHeader(tfd, parts->trunk_loc->offset);
      close(tfd);
    }
    if (!h.has_value() || h->type != kTrunkSlotData ||
        h->alloc_size != parts->trunk_loc->alloc_size ||
        h->file_size != parts->file_size || h->crc32 != parts->crc32) {
      Respond(c, 2);
      return;
    }
    st.st_size = static_cast<off_t>(h->file_size);
  } else {
    std::string local = ResolveLocal(group, remote);
    if (local.empty()) {
      Respond(c, 22);
      return;
    }
    int64_t lsize = LogicalSize(local);  // plain stat or recipe header
    if (lsize < 0) {
      Respond(c, 2);
      return;
    }
    st.st_size = static_cast<off_t>(lsize);
  }
  std::string body(40, '\0');
  uint8_t* out = reinterpret_cast<uint8_t*>(body.data());
  PutInt64BE(st.st_size, out);
  PutInt64BE(parts->create_timestamp, out + 8);
  PutInt64BE(parts->crc32, out + 16);
  std::string ip = UnpackIp(parts->source_ip);
  memcpy(out + 24, ip.data(), std::min<size_t>(ip.size(), 15));
  stats_.success_query++;
  Respond(c, 0, body);
}

void StorageServer::HandleSetMetadata(Conn* c) {
  stats_.total_set_meta++;
  // body: 16B group + 1B flag(O/M) + 8B name_len + name + metadata
  if (c->fixed.size() < 16 + 1 + 8) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  std::string group = GroupFromField(p);
  char flag = static_cast<char>(p[16]);
  int64_t name_len = GetInt64BE(p + 17);
  if (name_len <= 0 || name_len > 512 ||
      c->fixed.size() < 25 + static_cast<size_t>(name_len)) {
    Respond(c, 22);
    return;
  }
  std::string remote = c->fixed.substr(25, static_cast<size_t>(name_len));
  std::string meta = c->fixed.substr(25 + static_cast<size_t>(name_len));
  std::string local = ResolveLocal(group, remote);
  if (local.empty() || (flag != 'O' && flag != 'M')) {
    Respond(c, 22);
    return;
  }
  if (!RemoteExists(group, remote, local)) {
    Respond(c, 2);
    return;
  }
  std::string meta_path = local + "-m";
  if (flag == 'M') {
    // merge: existing records kept unless overwritten
    FILE* f = fopen(meta_path.c_str(), "r");
    if (f != nullptr) {
      std::string old;
      char buf[4096];
      size_t n;
      while ((n = fread(buf, 1, sizeof(buf), f)) > 0) old.append(buf, n);
      fclose(f);
      // naive merge: parse both, new wins
      auto parse = [](const std::string& s) {
        std::unordered_map<std::string, std::string> m;
        size_t pos = 0;
        while (pos < s.size()) {
          size_t rec_end = s.find('\x01', pos);
          if (rec_end == std::string::npos) rec_end = s.size();
          std::string rec = s.substr(pos, rec_end - pos);
          size_t sep = rec.find('\x02');
          if (sep != std::string::npos)
            m[rec.substr(0, sep)] = rec.substr(sep + 1);
          pos = rec_end + 1;
        }
        return m;
      };
      auto merged = parse(old);
      for (auto& [k, v] : parse(meta)) merged[k] = v;
      std::string out;
      for (auto& [k, v] : merged) {
        if (!out.empty()) out += '\x01';
        out += k + '\x02' + v;
      }
      meta = out;
    }
  }
  // Trunk files have no flat write that would have created the fan-out
  // dir their sidecar lives in.
  StoreManager::EnsureParentDirs(meta_path);
  if (!WriteSidecarAtomic(meta_path, meta)) {
    Respond(c, 5);
    return;
  }
  binlog_.Append(kBinlogOpUpdate, remote);
  stats_.success_set_meta++;
  stats_.last_source_update = time(nullptr);
  Respond(c, 0);
}

void StorageServer::HandleGetMetadata(Conn* c) {
  stats_.total_get_meta++;
  if (c->fixed.size() < 16 + 10) {
    Respond(c, 22);
    return;
  }
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  std::string group = GroupFromField(p);
  std::string remote = c->fixed.substr(16);
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  FILE* f = fopen((local + "-m").c_str(), "r");
  std::string meta;
  if (f != nullptr) {
    char buf[4096];
    size_t n;
    while ((n = fread(buf, 1, sizeof(buf), f)) > 0) meta.append(buf, n);
    fclose(f);
  } else if (!RemoteExists(group, remote, local)) {
    Respond(c, 2);
    return;
  }
  stats_.success_get_meta++;
  Respond(c, 0, meta);
}

// SYNC_APPEND_FILE / SYNC_MODIFY_FILE replica replay: writes a byte range
// into an existing file at an exact offset.  Two-stage fixed read like
// SYNC_CREATE; the range bytes then stream through kRecvFile straight into
// the target (no tmp file — replay is idempotent: a duplicate delivery
// rewrites the same bytes at the same offset).
bool StorageServer::BeginSyncRange(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t offset = GetInt64BE(p + kGroupNameMaxLen + 8);
  int64_t length = GetInt64BE(p + kGroupNameMaxLen + 16);
  if (c->fixed.size() == 40) {
    if (name_len <= 0 || name_len > 512 || offset < 0 || length < 0 ||
        c->pkg_len != 40 + name_len + length) {
      RespondError(c, 22);
      return false;
    }
    c->fixed_need = 40 + static_cast<size_t>(name_len);
    return true;  // keep reading the name (still kRecvFixed)
  }
  std::string group = GroupFromField(p);
  c->sync_remote = c->fixed.substr(40);
  std::string local = ResolveLocal(group, c->sync_remote);
  if (local.empty()) {
    RespondError(c, 22);
    return false;
  }
  if (!AcquireBusy(c, c->sync_remote)) {
    // The sync sender retries transiently-failed records, so EBUSY here
    // (client append racing the replay) resolves itself on the next pass.
    RespondError(c, 16 /*EBUSY*/);
    return false;
  }
  int fd = open(local.c_str(), O_WRONLY);
  if (fd < 0) {
    RespondError(c, static_cast<uint8_t>(errno == ENOENT ? 2 : 5));
    return false;
  }
  struct stat st;
  fstat(fd, &st);
  if (offset > st.st_size) {  // gap — out-of-order replay
    close(fd);
    RespondError(c, 22);
    return false;
  }
  if (lseek(fd, offset, SEEK_SET) != offset) {
    close(fd);
    RespondError(c, 5);
    return false;
  }
  c->file_fd = fd;
  c->range_offset = offset;
  c->file_size = length;
  c->file_remaining = length;
  c->state = ConnState::kRecvFile;
  return true;
}

// SYNC_UPDATE_FILE replica replay: refresh the metadata sidecar.
void StorageServer::HandleSyncUpdate(Conn* c) {
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < 32) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t meta_len = GetInt64BE(p + kGroupNameMaxLen + 8);
  if (name_len <= 0 || name_len > 512 || meta_len < 0 ||
      c->fixed.size() != 32 + static_cast<size_t>(name_len + meta_len)) {
    Respond(c, 22);
    return;
  }
  std::string remote = c->fixed.substr(32, static_cast<size_t>(name_len));
  std::string meta = c->fixed.substr(32 + static_cast<size_t>(name_len));
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  if (!RemoteExists(group, remote, local)) {
    Respond(c, 2);
    return;
  }
  StoreManager::EnsureParentDirs(local + "-m");
  if (!WriteSidecarAtomic(local + "-m", meta)) {
    Respond(c, 5);
    return;
  }
  binlog_.Append('u', remote);
  Respond(c, 0);
}

// TRUNCATE_FILE (client, appender files only) and SYNC_TRUNCATE_FILE
// (replica replay).  Same wire: 16B group + 8B name_len + 8B new_size +
// name.  Reference: storage_service.c:storage_server_truncate_file().
void StorageServer::HandleTruncate(Conn* c) {
  bool source = static_cast<StorageCmd>(c->cmd) == StorageCmd::kTruncateFile;
  if (source) stats_.total_append++;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() < 32) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t new_size = GetInt64BE(p + kGroupNameMaxLen + 8);
  if (name_len <= 0 || name_len > 512 || new_size < 0 ||
      c->fixed.size() != 32 + static_cast<size_t>(name_len)) {
    Respond(c, 22);
    return;
  }
  std::string remote = c->fixed.substr(32);
  std::string local = ResolveLocal(group, remote);
  if (local.empty()) {
    Respond(c, 22);
    return;
  }
  if (source) {
    // Only appender files are mutable (reference: EPERM on regular files).
    auto parts = DecodeFileId(group + "/" + remote);
    if (!parts.has_value() || !parts->appender) {
      Respond(c, 1 /*EPERM*/);
      return;
    }
  }
  // A truncate racing a mid-stream append/modify on the same file would
  // punch holes past the new EOF and desync the binlog from reality; the
  // per-file busy lock covers every mutation, truncate included.
  // (Released by ResetForNextRequest on every exit path.)
  if (!AcquireBusy(c, remote)) {
    Respond(c, 16 /*EBUSY*/);
    return;
  }
  if (truncate(local.c_str(), new_size) != 0) {
    Respond(c, static_cast<uint8_t>(errno == ENOENT ? 2 : 5));
    return;
  }
  binlog_.Append(source ? kBinlogOpTruncate : 't', remote,
                 std::to_string(new_size));
  if (source) {
    stats_.success_append++;
    stats_.last_source_update = time(nullptr);
  }
  Respond(c, 0);
}

// APPEND_FILE / MODIFY_FILE: client-side mutation of an appender file.
// APPEND wire:  16B group + 8B name_len + 8B length + name + bytes.
// MODIFY wire:  16B group + 8B name_len + 8B offset + 8B length + name +
// bytes.  Reference: storage_service.c:storage_append_file() /
// storage_modify_file().
bool StorageServer::BeginClientRange(Conn* c) {
  bool is_append = static_cast<StorageCmd>(c->cmd) == StorageCmd::kAppendFile;
  const size_t prefix = is_append ? 32 : 40;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t name_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t offset = is_append ? -1 : GetInt64BE(p + kGroupNameMaxLen + 8);
  int64_t length = GetInt64BE(p + kGroupNameMaxLen + (is_append ? 8 : 16));
  if (c->fixed.size() == prefix) {
    if (name_len <= 0 || name_len > 512 || length < 0 ||
        (!is_append && offset < 0) ||
        c->pkg_len != static_cast<int64_t>(prefix) + name_len + length) {
      RespondError(c, 22);
      return false;
    }
    c->fixed_need = prefix + static_cast<size_t>(name_len);
    return true;  // keep reading the name
  }
  std::string group = GroupFromField(p);
  c->sync_remote = c->fixed.substr(prefix);
  std::string local = ResolveLocal(group, c->sync_remote);
  auto parts = DecodeFileId(group + "/" + c->sync_remote);
  if (local.empty() || !parts.has_value() || !parts->appender) {
    RespondError(c, 1 /*EPERM: not an appender file*/);
    return false;
  }
  if (!AcquireBusy(c, c->sync_remote)) {
    RespondError(c, 16 /*EBUSY: concurrent mutation of this file*/);
    return false;
  }
  int fd = open(local.c_str(), O_WRONLY);
  if (fd < 0) {
    RespondError(c, static_cast<uint8_t>(errno == ENOENT ? 2 : 5));
    return false;
  }
  struct stat st;
  fstat(fd, &st);
  if (offset < 0) offset = st.st_size;  // append lands at EOF
  if (offset > st.st_size) {
    close(fd);
    RespondError(c, 22);
    return false;
  }
  if (lseek(fd, offset, SEEK_SET) != offset) {
    close(fd);
    RespondError(c, 5);
    return false;
  }
  c->file_fd = fd;
  c->range_offset = offset;
  c->file_size = length;
  c->file_remaining = length;
  c->state = ConnState::kRecvFile;
  return true;
}

// UPLOAD_SLAVE_FILE: store a derived file under the master's name stem
// plus a prefix ("<stem><prefix>.<ext>"), so clients can address it from
// the master ID alone.  Wire: 16B group + 8B master_len + 8B size +
// 16B prefix + 6B ext + master_name + bytes.  Reference:
// storage_service.c:storage_upload_slave_file() (cmd 21).
bool StorageServer::BeginSlaveUpload(Conn* c) {
  const size_t kPrefixLen = 16 + 8 + 8 + 16 + 6;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  int64_t master_len = GetInt64BE(p + kGroupNameMaxLen);
  int64_t size = GetInt64BE(p + kGroupNameMaxLen + 8);
  if (c->fixed.size() == kPrefixLen) {
    if (master_len <= 0 || master_len > 512 || size < 0 ||
        c->pkg_len != static_cast<int64_t>(kPrefixLen) + master_len + size) {
      RespondError(c, 22);
      return false;
    }
    c->fixed_need = kPrefixLen + static_cast<size_t>(master_len);
    return true;
  }
  std::string group = GroupFromField(p);
  c->slave_prefix = GetFixedField(p + kGroupNameMaxLen + 16, 16);
  c->ext = ExtFromField(p + kGroupNameMaxLen + 32);
  std::string master = c->fixed.substr(kPrefixLen);
  std::string master_local = ResolveLocal(group, master);
  auto parts = DecodeFileId(group + "/" + master);
  if (master_local.empty() || !parts.has_value() ||
      c->slave_prefix.empty() || !parts->prefix.empty() /*no slave-of-slave*/ ||
      !RemoteExists(group, master, master_local) /*trunk-aware*/) {
    RespondError(c, 22);
    return false;
  }
  // Derived name: master path with "<stem><prefix>[.ext]" as the filename.
  size_t slash = master.rfind('/');
  size_t dot = master.find('.', slash);
  std::string stem = dot == std::string::npos ? master : master.substr(0, dot);
  c->sync_remote = stem + c->slave_prefix;
  if (!c->ext.empty()) c->sync_remote += "." + c->ext;
  if (ResolveLocal(group, c->sync_remote).empty()) {
    RespondError(c, 22);  // prefix/ext failed name validation
    return false;
  }
  sscanf(c->sync_remote.c_str(), "M%02X/", &c->store_path_index);
  c->file_size = size;
  c->file_remaining = size;
  c->crc32 = 0;
  c->hashing = false;
  c->tmp_path = store_.NewTmpPath(c->store_path_index);
  c->file_fd = open(c->tmp_path.c_str(), O_CREAT | O_WRONLY | O_TRUNC, 0644);
  if (c->file_fd < 0) {
    RespondError(c, 5);
    return false;
  }
  c->state = ConnState::kRecvFile;
  return true;
}

void StorageServer::FinishSlaveUpload(Conn* c) {
  close(c->file_fd);
  c->file_fd = -1;
  std::string local = ResolveLocal(cfg_.group_name, c->sync_remote);
  StoreManager::EnsureParentDirs(local);
  // A slave name is deterministic — refuse to silently clobber an existing
  // slave (reference returns EEXIST).
  struct stat st;
  if (stat(local.c_str(), &st) == 0) {
    unlink(c->tmp_path.c_str());
    c->tmp_path.clear();
    Respond(c, 17 /*EEXIST*/);
    return;
  }
  if (rename(c->tmp_path.c_str(), local.c_str()) != 0) {
    unlink(c->tmp_path.c_str());
    c->tmp_path.clear();
    Respond(c, 5);
    return;
  }
  c->tmp_path.clear();
  binlog_.Append(kBinlogOpCreate, c->sync_remote);
  stats_.success_upload++;
  stats_.last_source_update = time(nullptr);
  NoteHeat(c, HeatOp::kUpload, cfg_.group_name + "/" + c->sync_remote);
  Respond(c, 0, PackGroupField(cfg_.group_name) + c->sync_remote);
}

// CREATE_LINK (client, cmd 20) and SYNC_CREATE_LINK (replica replay).
// Body: 16B group + target_remote \x02 src_remote; creates a hard link so
// the target shares the source's bytes (the dedup path uses the same
// mechanism internally).
void StorageServer::HandleCreateLink(Conn* c) {
  bool source = static_cast<StorageCmd>(c->cmd) == StorageCmd::kCreateLink;
  const uint8_t* p = reinterpret_cast<const uint8_t*>(c->fixed.data());
  if (c->fixed.size() <= static_cast<size_t>(kGroupNameMaxLen)) {
    Respond(c, 22);
    return;
  }
  std::string group = GroupFromField(p);
  std::string rest = c->fixed.substr(kGroupNameMaxLen);
  size_t sep = rest.find('\x02');
  if (group != cfg_.group_name || sep == std::string::npos) {
    Respond(c, 22);
    return;
  }
  std::string target = rest.substr(0, sep);
  std::string src = rest.substr(sep + 1);
  std::string tl = ResolveLocal(group, target);
  std::string sl = ResolveLocal(group, src);
  if (tl.empty() || sl.empty()) {
    Respond(c, 22);
    return;
  }
  StoreManager::EnsureParentDirs(tl);
  if (link(sl.c_str(), tl.c_str()) != 0 && errno != EEXIST) {
    // Chunked source: "linking" means duplicating the (tiny) recipe and
    // taking a reference on each chunk.
    bool linked = false;
    if (errno == ENOENT) {
      auto r = LoadRecipeFor(sl);
      ChunkStore* cs = StoreForLocal(sl);
      ChunkStore* tcs = StoreForLocal(tl);
      if (r.has_value() && cs != nullptr && cs->RefAll(*r)) {
        std::string err;
        // Store through the TARGET path's store so LoadRecipeFor(tl)
        // finds it in the same slab index it will later consult.
        bool stored = tcs != nullptr
                          ? tcs->StoreRecipe(tl + ".rcp", *r, &err)
                          : WriteRecipeFile(tl + ".rcp", *r, &err);
        if (stored) {
          linked = true;
        } else {
          cs->UnrefAll(*r);
          FDFS_LOG_ERROR("link recipe copy: %s", err.c_str());
        }
      }
    }
    if (!linked) {
      Respond(c, static_cast<uint8_t>(errno == ENOENT ? 2 : 5));
      return;
    }
  }
  binlog_.Append(source ? kBinlogOpLink : 'l', target, src);
  if (source) stats_.last_source_update = time(nullptr);
  Respond(c, 0);
}

}  // namespace fdfs
