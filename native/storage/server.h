// Storage daemon: epoll nio loop + request handlers + upload pipeline.
//
// Reference map (SURVEY.md §2.2):
// - connection state machine / stage flags → storage/storage_nio.c
//   (client_sock_read/client_sock_write, FDFS_STORAGE_STAGE_NIO_*)
// - per-command handlers → storage/storage_service.c
//   (storage_deal_task, storage_upload_file, storage_server_download_file…)
// - chunked disk IO with rolling checksum → storage/storage_dio.c
//   (dio_write_file: the loop the dedup plugin instruments)
// - binlog on every mutation → storage/storage_sync.c:storage_binlog_write
#pragma once

#include <sys/epoll.h>

#include <array>
#include <atomic>
#include <cstdio>
#include <cstdint>
#include <memory>
#include <mutex>

#include "common/lockrank.h"
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include "common/eventlog.h"
#include "common/heatsketch.h"
#include "common/metrog.h"
#include "common/sloeval.h"
#include "common/stats.h"
#include "common/trace.h"
#include "common/workers.h"

#include "common/bytes.h"
#include "common/protocol_gen.h"
#include "common/net.h"
#include "storage/admission.h"
#include "storage/binlog.h"
#include "storage/chunkstore.h"
#include "storage/config.h"
#include "storage/dedup.h"
#include "storage/hotrepl.h"
#include "storage/ingest.h"
#include "storage/recovery.h"
#include "storage/rebalance.h"
#include "storage/scrub.h"
#include "storage/store.h"
#include "storage/sync.h"
#include "storage/tracker_client.h"
#include "storage/trunk.h"

namespace fdfs {

// Per-op counters (reference: FDFSStorageStat in tracker/tracker_types.h,
// reported to the tracker with each beat).  Atomics: written by the nio
// loop, snapshotted by the tracker-reporter thread.
struct StorageStats {
  std::atomic<int64_t> total_upload{0}, success_upload{0};
  std::atomic<int64_t> total_download{0}, success_download{0};
  std::atomic<int64_t> total_delete{0}, success_delete{0};
  std::atomic<int64_t> total_append{0}, success_append{0};
  std::atomic<int64_t> total_set_meta{0}, success_set_meta{0};
  std::atomic<int64_t> total_get_meta{0}, success_get_meta{0};
  std::atomic<int64_t> total_query{0}, success_query{0};
  std::atomic<int64_t> dedup_hits{0};
  std::atomic<int64_t> dedup_bytes_saved{0};
  std::atomic<int64_t> bytes_uploaded{0}, bytes_downloaded{0};
  std::atomic<int64_t> last_source_update{0};  // ts of last client mutation

  // Restart-safe counters (reference: storage_write_to_stat_file() /
  // data/storage_stat.dat).
  bool SaveToFile(const std::string& path) const;
  bool LoadFromFile(const std::string& path);

  // Restart-persisted slot count: slots [0, kPersisted) of the beat blob
  // (protocol_gen.h kBeatStatNames) come from this struct; the server's
  // beat callback fills the live slots above it.
  static constexpr int kPersisted = 19;

  // Beat-blob prefix (shared contract with tracker/cluster.cc JSON).
  // Writes exactly kPersisted slots; the caller owns the rest.
  void Snapshot(int64_t* out) const {
    out[0] = total_upload; out[1] = success_upload;
    out[2] = total_download; out[3] = success_download;
    out[4] = total_delete; out[5] = success_delete;
    out[6] = total_append; out[7] = success_append;
    out[8] = total_set_meta; out[9] = success_set_meta;
    out[10] = total_get_meta; out[11] = success_get_meta;
    out[12] = total_query; out[13] = success_query;
    out[14] = bytes_uploaded; out[15] = bytes_downloaded;
    out[16] = dedup_hits; out[17] = dedup_bytes_saved;
    out[18] = last_source_update;
  }
};

class StorageServer {
 public:
  explicit StorageServer(StorageConfig cfg);
  ~StorageServer();

  bool Init(std::string* error);
  void Run();
  void Stop();
  EventLoop& loop() { return loop_; }
  const StorageStats& stats() const { return stats_; }
  StatsRegistry& registry() { return registry_; }
  const StorageConfig& config() const { return cfg_; }
  BinlogWriter& binlog() { return binlog_; }
  TrackerReporter* reporter() { return reporter_.get(); }
  void DumpState();  // SIGUSR1 analogue of storage_dump.c

 private:
  enum class ConnState { kRecvHeader, kRecvFixed, kRecvFile, kSend };

  struct NioThread;  // one epoll loop + its connections (storage_nio.c)

  // Streaming source for recipe (chunked-file) downloads, assembled
  // scatter-gather (the PR 5 read-path overhaul): per refill round a
  // bounded batch of spans is staged — cache-hit spans REFERENCE the
  // read cache's shared buffers (zero copy), cold spans pread into one
  // pooled buffer (reused across rounds; its capacity is the only
  // steady-state allocation) — and the whole batch flushes to the
  // socket via one sendmsg iovec per round.  A multi-GB logical file
  // never occupies more than one batch of memory and never stalls the
  // loop's other connections (the reference's dio read loop).
  struct RecipeStream {
    struct Span {
      // Cache-hit spans hold the cache entry alive via `owner` (an
      // eviction or invalidation mid-send cannot free the bytes);
      // cold spans index into `pool` (offset, not pointer — the pool
      // resizes once per round BEFORE any span is flushed).
      std::shared_ptr<const std::string> owner;
      size_t off = 0;   // offset into *owner or pool
      size_t len = 0;
    };
    Recipe recipe;
    ChunkStore* cs = nullptr;
    size_t idx = 0;          // next recipe entry
    int64_t skip = 0;        // bytes to skip inside entry `idx` (range start)
    int64_t remaining = 0;   // logical bytes still to send
    bool pinned = false;
    std::vector<Span> spans;   // current round, [span_idx..) unsent
    size_t span_idx = 0;
    size_t span_off = 0;       // progress inside spans[span_idx]
    std::string pool;          // cold-read buffer for the current round
    bool HasPending() const { return span_idx < spans.size(); }
    // Pins (ChunkStore::PinRecipe) keep the chunks on disk while the
    // stream is in flight even if the file is deleted concurrently —
    // the POSIX open-fd guarantee flat files get from sendfile.
    ~RecipeStream() {
      if (pinned && cs != nullptr) cs->UnpinRecipe(recipe);
    }
  };

  // Negotiated-upload session (UPLOAD_RECIPE -> UPLOAD_CHUNKS): phase 1
  // parked the parsed recipe here with a pin on every chunk (present
  // ones must survive concurrent delete/GC until the commit references
  // them).  Owned by ingest_sessions_ between the two requests; phase 2
  // takes it out (one commit per session), and the sweep timer expires
  // sessions whose client vanished.  The destructor unpins, so every
  // exit path — commit, abort, timeout, shutdown — releases the pins.
  struct UploadSession {
    int64_t id = 0;
    int spi = 0;
    std::string ext;
    uint32_t crc32 = 0;
    Recipe recipe;           // full chunk list (lengths pre-validated)
    std::string needed;      // phase-1 bitmap (1 = client ships)
    int64_t needed_bytes = 0;
    ChunkStore* cs = nullptr;
    int64_t deadline_s = 0;  // wall-clock expiry (sweep timer)
    ~UploadSession() {
      if (cs != nullptr) cs->UnpinRecipe(recipe);
    }
  };

  struct Conn {
    int fd = -1;
    ConnState state = ConnState::kRecvHeader;
    // recv
    uint8_t header[kHeaderSize];
    size_t header_got = 0;
    int64_t pkg_len = 0;
    uint8_t cmd = 0;
    std::string fixed;          // in-memory body (or fixed prefix for upload)
    size_t fixed_need = 0;
    int64_t body_consumed = 0;  // bytes of pkg_len read so far
    bool close_after_send = false;  // early error left unread request bytes
    // upload streaming
    int file_fd = -1;
    std::string tmp_path;
    int64_t file_remaining = 0;
    int64_t file_size = 0;
    int store_path_index = 0;
    std::string ext;
    Sha1Stream sha1;
    uint32_t crc32 = 0;
    bool hashing = false;
    uint8_t replica_op = 0;     // set for SYNC_* ops (no binlog re-emit)
    std::string sync_remote;    // target remote filename for SYNC_CREATE
    int64_t range_offset = 0;   // append/modify replay write position
    std::string slave_prefix;   // UPLOAD_SLAVE_FILE name prefix
    bool discarding = false;    // draining a rejected request's body bytes
    uint8_t pending_status = 0; // error to send once the drain completes
    std::string pending_body;   // response body for that error (shed hint)
    std::string busy_key;       // in-place-mutated file this conn holds
    // send
    std::string out;
    size_t out_off = 0;
    int send_fd = -1;
    int64_t send_off = 0;
    int64_t send_remaining = 0;
    std::unique_ptr<RecipeStream> rstream;  // chunked download source
    // threading
    NioThread* owner = nullptr;   // the nio loop this conn lives on
    bool async_pending = false;   // a dio worker owns the request right now
    bool dead = false;            // closed while async_pending: zombie
    // access log bookkeeping (SURVEY.md §5: the rebuild logs recv/work
    // splits, not just the total)
    int64_t req_start_us = 0;
    int64_t work_start_us = 0;  // dio-stage begin (fingerprint/write)
    // What this request did, stage by stage as it happened (body
    // receive, dio queue wait, and per segment: tmp read-back,
    // fingerprint with its chunker / lock wait / RPC, chunk-store
    // writes, the negotiated commit's verify / present / re-index, the
    // binlog append).  Written by the one thread that works on the
    // request at the time; the access log's stage columns and the
    // ingest histograms are its sums, the span ring and the access log's
    // "stages" line its intervals (common/trace.h).
    StageTrace stages;
    std::string peer_ip;
    // Negotiated upload (UPLOAD_CHUNKS): the session this request
    // commits, plus the missing/total split RecordRequestSpans turns
    // into the ingest.chunks trace annotation (set by both phases).
    int64_t ingest_session = 0;
    int64_t ingest_chunks_total = 0;
    int64_t ingest_chunks_missing = 0;
    // Hot-key heat telemetry: handlers that resolve a file-id stamp it
    // here (with the op class) so LogAccess — the accounting choke
    // point — feeds the heat sketch exactly once per request.
    std::string heat_key;
    uint8_t heat_op = 0;  // HeatOp
    // Distributed tracing: context from a TRACE_CTX prefix frame,
    // consumed by the next request (ResetForNextRequest clears it).
    // trace_span is the request's root span id, allocated when the
    // frame completes so mutation paths can correlate (binlog ->
    // replication) before the span itself is recorded at LogAccess.
    TraceCtx trace_ctx;
    bool traced = false;
    uint32_t trace_span = 0;
    // Request QoS: class from a PRIORITY prefix frame (kPriorityUntagged
    // = none seen; the dispatch then defaults by opcode).  Consumed by
    // the next request like trace_ctx.  resolved_priority is the class
    // the admission consult actually used, kept for the access log.
    uint8_t priority = 0xFF;
    uint8_t resolved_priority = 0;
    // Bytes this request added to the server-wide in-flight ledger at
    // admission (its pkg_len); subtracted exactly once when the request
    // finishes (LogAccess) or the conn dies mid-request (CloseConn).
    int64_t inflight_acct = 0;
    // This request was refused by the admission ladder: keep it out of
    // the per-opcode count/error/latency stats — a shed EBUSY feeding
    // the error_rate_pct SLO would hold the breach (= pressure 1.0)
    // active and the ladder could never relax off its own refusals.
    // The admission controller's shed counters carry the accounting.
    bool shed_resp = false;
  };

  struct NioThread {
    std::unique_ptr<EventLoop> loop;
    std::thread thread;
    std::unordered_map<int, std::unique_ptr<Conn>> conns;  // loop-thread only
    std::vector<std::unique_ptr<Conn>> zombies;            // await dio done
    // Cumulative handler time, fed by this loop's iteration hook and
    // read by the metrics tick for nio.loop_busy_pct.<i> (the per-loop
    // duty cycle the shared loop-lag histogram cannot attribute).
    std::atomic<int64_t> busy_us{0};
    // Sharded accept (ISSUE 18): this reactor's own SO_REUSEPORT
    // listening fd (-1 in round-robin fallback mode, where the main
    // loop accepts and posts).
    int listen_fd = -1;
    // Per-reactor spread telemetry, fed by BOTH accept modes (the
    // reactor's own accept handler, or the main-loop round-robin
    // assignment) so nio.accepts.<i> / nio.conns.<i> always mean "this
    // reactor's share".  Read by gauge-fns under the registry mutex —
    // atomics only.
    std::atomic<int64_t> accepts{0};
    std::atomic<int64_t> live_conns{0};
  };
  // Honest divergence from the reference's fast_task_queue.c pooled-task
  // buffers: each Conn owns its recv/send std::strings, which retain
  // their capacity across requests on a kept-alive connection — the
  // steady-state allocation behavior of the pool without the free-list.
  // The queue half of fast_task_queue maps to WorkerPool (workers.h).

  // -- nio ---------------------------------------------------------------
  EventLoop* ConnLoop(Conn* c) { return c->owner ? c->owner->loop.get() : &loop_; }
  void AdoptConn(NioThread* t, int fd);   // runs on t's loop thread
  // Hand the rest of the current request to the store path's dio pool;
  // `work` runs on a worker (it may build a response via Respond but must
  // not touch the socket/epoll), then the conn resumes on its loop.
  void OffloadToDio(Conn* c, int spi, std::function<void()> work);
  void OnAccept(uint32_t events);
  // Reactor-owned accept (reuseport mode): runs ON t's loop thread, so
  // the accepted conn is adopted inline — no cross-loop Post.
  void OnReactorAccept(NioThread* t);
  // Shared accept tail of both modes: cap refusal + first-conn local-ip
  // capture.  Returns false when the conn was refused (and closed).
  bool AdmitConn(int fd);
  void OnConnEvent(Conn* c, uint32_t events);
  void ReadConn(Conn* c);
  bool WriteConn(Conn* c);          // false => conn closed
  void CloseConn(Conn* c);
  void ResetForNextRequest(Conn* c);
  void Respond(Conn* c, uint8_t status, const std::string& body = "");
  // Stage the next scatter-gather batch of a recipe download (cache
  // lookups + pooled cold preads); false => a chunk vanished mid-stream
  // (caller aborts the connection — the header already went out).
  bool RefillRecipeSpans(RecipeStream* rs);
  // Flush staged spans with sendmsg; same contract as WriteConn's other
  // stages: true = keep going / parked on EPOLLOUT, false = conn closed.
  enum class FlushResult { kDone, kBlocked, kError };
  FlushResult FlushRecipeSpans(Conn* c, RecipeStream* rs);
  // Error response that may leave unread request bytes: drains them (the
  // connection stays usable) and rolls back any in-flight file write.
  void RespondError(Conn* c, uint8_t status);
  // Admission shed: EBUSY carrying the 8-byte BE retry-after-ms hint —
  // RespondError's drain discipline, plus a staged response body.
  void ShedRequest(Conn* c, int64_t retry_after_ms);
  void AbortFileOp(Conn* c);
  // Per-file writer exclusion for streamed in-place mutations: two appends
  // to one appender file interleaving across epoll rounds would corrupt it.
  bool AcquireBusy(Conn* c, const std::string& remote);
  void ReleaseBusy(Conn* c);
  void RespondFile(Conn* c, uint8_t status, int file_fd, int64_t offset,
                   int64_t count);
  // Access log (storage.conf:use_access_log; reference: the per-request
  // "op client_ip status bytes cost_us" lines storage_service.c emits).
  // Also the per-request accounting choke point: every response path runs
  // through here exactly once (req_start_us guards re-entry), so the
  // stats registry's per-opcode counters and latency histograms update
  // here regardless of whether the access log is enabled.
  void LogAccess(Conn* c, uint8_t status, int64_t bytes);
  // Stamp the request's heat-sketch attribution (file-id key + op
  // class); LogAccess feeds the sketch from it exactly once.
  void NoteHeat(Conn* c, HeatOp op, const std::string& key);

  // -- stats registry (common/stats.h; STAT opcode) ----------------------
  // Pre-register per-opcode counters/histograms and the gauge mirrors of
  // live state so hot paths only touch cached atomic pointers.
  void InitStatsRegistry();
  // -- tracing (common/trace.h; TRACE_CTX / TRACE_DUMP opcodes) ----------
  // Retain this request's spans (root + stage children) when it is
  // traced or exceeded the slow threshold; called from LogAccess (the
  // per-request accounting choke point).
  void RecordRequestSpans(Conn* c, uint8_t status, int64_t now_us,
                          int64_t bytes);
  // Remember a traced mutation's context keyed by remote filename so
  // the replication sender stitches the sync hop into the same trace.
  void NoteTracedMutation(Conn* c, const std::string& remote);
  // Refresh the per-peer sync gauges (peers come and go, so these are
  // plain gauges re-set — and pruned — at snapshot time) ahead of a
  // STAT serialization or a metrics-journal tick.
  void RefreshPeerGauges();
  // Refresh snapshot-time gauges (per-peer sync lag) and serialize.
  std::string BuildStatsJson();
  // Metrics tick (slo_eval_interval_s): snapshot the registry, append
  // to the metrics journal, and evaluate the SLO rule table against the
  // previous tick's snapshot (common/metrog.h, common/sloeval.h).
  void MetricsTick();
  // Beat callback: persisted prefix from stats_, live slots from the
  // registry/subsystems (fills kBeatStatCount slots).
  void FillBeatStats(int64_t* out);
  int64_t MaxSyncLagS() const;
  // statvfs every store path and cache the fullest-path percentage.
  // Called at startup, each metrics tick (main loop), and each beat
  // (tracker-client thread) — NEVER from the store.disk_used_pct
  // gauge-fn itself: gauge-fns run under the registry mutex on the nio
  // loop, and statvfs on a stalled mount can block for seconds.
  void RefreshDiskUsedPct();
  // -- gray-failure health layer (common/healthmon.h; HEALTH_STATUS) -----
  // Dedicated "health.probe" thread: every health_probe_interval_s it
  // ACTIVE_TESTs the trackers + the group's sync peers (feeding the
  // passive per-peer table through the NetRpc observer) and runs the
  // per-store-path disk probes (4 KB tmp-write+fsync + read-back) —
  // off the request path, the store.disk_used_pct discipline.
  void HealthProbeMain();
  void RunHealthProbes();
  // HEALTH_STATUS wire body (healthmon Json: peer table + probes +
  // watchdog counts).
  std::string HealthStatusJson();

  // -- dispatch ----------------------------------------------------------
  void OnHeaderComplete(Conn* c);
  void OnFixedComplete(Conn* c);
  void OnFileComplete(Conn* c);
  void SyncCreateComplete(Conn* c);  // replica create (dio worker)
  // Chunk-aware replication receiver (SYNC_QUERY_CHUNKS /
  // SYNC_CREATE_RECIPE): answer which chunks are missing, then build
  // the replica from refs + shipped payloads.
  void HandleSyncQueryChunks(Conn* c);
  void SyncRecipeComplete(Conn* c);  // dio worker
  // Chunk-aware disk-recovery servers (FETCH_RECIPE / FETCH_CHUNK): let
  // a rebuilding peer pull recipes and only the chunk bytes it lacks.
  void HandleFetchRecipe(Conn* c);
  void HandleFetchChunk(Conn* c);
  // Erasure-coded cold tier (EC_RELEASE receiver + the released-chunk
  // remote read hook installed on every chunk store).
  void HandleEcRelease(Conn* c);       // dio worker
  bool FetchChunkFromPeers(int spi, const std::string& digest_hex,
                           int64_t len, std::string* out);
  // Dedup-aware negotiated upload (UPLOAD_RECIPE / UPLOAD_CHUNKS; both
  // run on the store path's dio pool): phase 1 probes + pins + parks a
  // session, phase 2 verifies the shipped chunks and assembles the file.
  void HandleUploadRecipe(Conn* c);    // dio worker
  bool BeginUploadChunks(Conn* c);     // nio: parse prefix, open tmp
  void UploadChunksComplete(Conn* c);  // dio worker
  std::unique_ptr<UploadSession> TakeIngestSession(int64_t id);
  void SweepIngestSessions();          // timer: expire vanished clients
  void DeleteWork(Conn* c);          // delete body (dio worker)

  // -- handlers (storage_service.c analogues) ----------------------------
  bool BeginUpload(Conn* c);        // parse fixed, open tmp file
  void FinishUpload(Conn* c);       // mint id, dedup, commit, binlog
  // A new file's bytes or recipe are down: its binlog record (the
  // request's storage.binlog interval), then the reply naming it.
  void AckCreated(Conn* c, const std::string& remote);
  void HandleDownload(Conn* c);
  void HandleDelete(Conn* c);
  void HandleQueryFileInfo(Conn* c);
  void HandleNearDups(Conn* c);
  void HandleSetMetadata(Conn* c);
  void HandleGetMetadata(Conn* c);
  bool BeginClientRange(Conn* c);   // APPEND_FILE / MODIFY_FILE
  void HandleTruncate(Conn* c);     // TRUNCATE_FILE (+ sync replay path)
  bool BeginSlaveUpload(Conn* c);   // UPLOAD_SLAVE_FILE prefix parse
  void FinishSlaveUpload(Conn* c);
  void HandleCreateLink(Conn* c);   // CREATE_LINK + SYNC_CREATE_LINK
  void HandleSyncUpdate(Conn* c);
  bool BeginSyncRange(Conn* c);     // SYNC_APPEND / SYNC_MODIFY prefix parse

  std::string MintFileId(int spi, int64_t size, uint32_t crc,
                         const std::string& ext, bool appender,
                         const TrunkLocation* trunk_loc = nullptr);
  // -- trunk integration (storage/trunk_mgr analogues) -------------------
  void RefreshClusterParams();       // 1s timer: params + trunk role
  bool TrunkEligible(int64_t size) const;
  // Allocate a slot locally (trunk server) or via RPC; nullopt => caller
  // falls back to a flat file.
  std::optional<TrunkLocation> TrunkAlloc(int64_t payload_size);
  void TrunkFree(const TrunkLocation& loc);
  // Store tmp-file content into a trunk slot and mint the ID; "" on
  // failure (caller falls back to flat).
  std::string TrunkStoreUpload(Conn* c);
  void HandleTrunkRpc(Conn* c);      // cmds 27/28/29 server side
  void HandleFetchOnePathBinlog(Conn* c);  // cmd 26 (disk-recovery feed)
  void HandleTrunkDownload(Conn* c, const FileIdParts& parts, int64_t offset,
                           int64_t count);
  // Resolve "group/remote" or "remote" to a local path; empty on error.
  std::string ResolveLocal(const std::string& group,
                           const std::string& remote) const;
  // Existence check that understands trunk names: flat inode present, or
  // the trunk slot is live with this ID's exact identity.
  bool RemoteExists(const std::string& group, const std::string& remote,
                    const std::string& local);
  std::string MyIp() const;

  // -- chunk-level dedup (north star; chunkstore.h) ----------------------
  // Whether this upload takes the chunked path (plugin active, chunking
  // enabled, size over threshold).
  bool ChunkEligible(int64_t size) const;
  ChunkStore* StoreForLocal(const std::string& local) const;
  // Slab-aware recipe access for call sites that may lack a chunk store
  // (dedup off): route through the store's recipe codec (slab record or
  // flat sidecar) when one exists, else the flat .rcp file directly.
  std::optional<Recipe> LoadRecipeFor(const std::string& local) const;
  bool RecipeExistsFor(const std::string& local) const;
  // What an ingest (ingest.h) into store path `spi` runs against: the
  // plugin of the calling thread, the stage recorder of its request.
  IngestTarget IngestFor(int spi, DedupPlugin* plugin,
                         StageTrace* stages) const;
  // Open the logical content at `local`: a plain fd, or a recipe
  // materialized into an unlinked temp file.  -1 when missing.
  int OpenLogical(const std::string& local, int64_t* size);
  // Logical size without opening (plain stat or recipe header); -1 when
  // missing.
  int64_t LogicalSize(const std::string& local) const;
  // Delete logical content: plain unlink, or recipe removal + chunk
  // unref.  Returns errno-style status (0 ok, 2 missing, 5 io).
  int RemoveLogical(const std::string& local, const std::string& file_ref);
  // True when the tracker marked this group draining/retired in the
  // beat trailer: new-file uploads answer EBUSY (reads, replication,
  // and the migrator's loopback ops stay allowed).
  bool DrainingRefusal() const;

  StorageConfig cfg_;
  StoreManager store_;
  BinlogWriter binlog_;
  std::unique_ptr<DedupPlugin> dedup_;
  std::unique_ptr<DedupPlugin> recovery_dedup_;  // recovery-thread instance
  // One content-addressed chunk store per store path (chunk-level dedup).
  std::vector<std::unique_ptr<ChunkStore>> chunk_stores_;
  // Integrity engine: background scrub/quarantine/repair/GC over the
  // chunk stores (storage/scrub.h; SCRUB_STATUS / SCRUB_KICK opcodes).
  // scrub_dedup_ is the scrub thread's own sidecar plugin instance for
  // the batched DEDUP_VERIFY path (plugins are not thread-safe).
  std::unique_ptr<DedupPlugin> scrub_dedup_;
  std::unique_ptr<ScrubManager> scrub_;
  // Rebalance migrator (ISSUE 11): drains this group's files into
  // their jump-hash target groups once the tracker marks the group
  // DRAINING (storage/rebalance.h; rebalance_* beat slots).
  std::unique_ptr<RebalanceManager> rebalance_;
  std::unique_ptr<TrackerReporter> reporter_;
  std::unique_ptr<SyncManager> sync_;
  std::unique_ptr<RecoveryManager> recovery_;
  // Hot-replication fan-out worker (ISSUE 20): runs the tracker's
  // replicate/drop elections delivered in beat-response trailers.
  std::unique_ptr<HotReplManager> hotrepl_;
  EventLoop loop_;                      // main: accept + timers
  int listen_fd_ = -1;
  // nio work threads (storage.conf:work_threads); each reactor owns the
  // connections it accepts for their whole lifetime (reference:
  // storage_nio.c per-thread epoll loops).  With nio_reuseport active
  // every reactor accepts on its own SO_REUSEPORT listener; otherwise
  // the main loop accepts and assigns round-robin.
  std::vector<std::unique_ptr<NioThread>> nio_;
  bool reuseport_active_ = false;       // set once in Init
  size_t next_nio_ = 0;                 // main-loop only (accept)
  std::atomic<int64_t> conn_count_{0};
  std::atomic<int64_t> refused_conn_count_{0};  // over max_connections
  std::atomic<int64_t> disk_used_pct_{0};       // RefreshDiskUsedPct cache
  // Filesystem inodes in use across the store paths (deduped by fsid),
  // refreshed with disk_used_pct_ OFF the registry lock — the
  // store.inodes_used gauge is what the slab-packing win (ISSUE 9) is
  // judged against on small-file corpora.
  std::atomic<int64_t> inodes_used_{0};
  // Gray-failure health layer (ISSUE 17).  Probe latencies are the
  // worst store path's most recent round (gauge-fns read the atomics,
  // never the disk — the disk_used_pct discipline); stalled_threads_
  // mirrors the last watchdog scan for the watchdog.stalled_threads
  // gauge.  probe_slow_noted_ is probe-thread-only state for
  // one-disk.gray-event-per-outage.
  std::atomic<int64_t> probe_read_us_{0};
  std::atomic<int64_t> probe_write_us_{0};
  std::atomic<int64_t> stalled_threads_{0};
  std::atomic<bool> health_stop_{false};
  std::thread health_probe_thread_;
  std::thread inject_stall_thread_;  // watchdog_inject_stall_ms debug aid
  std::vector<bool> probe_slow_noted_;  // per store path; probe thread only
  // dio pools, one per store path (storage.conf:disk_writer_threads;
  // reference: storage_dio.c per-path reader/writer queues).
  std::vector<std::unique_ptr<WorkerPool>> dio_pools_;
  int dio_workers_per_path_ = 0;  // fixed in Init (workers.h: the rule)
  RankedMutex busy_mu_{LockRank::kBusyFiles};
  std::unordered_set<std::string> busy_files_;  // remote names being mutated
  RankedMutex log_mu_{LockRank::kAccessLog};  // access_log_ writes
  StorageStats stats_;
  // Named-stat registry behind the STAT opcode.  Per-opcode handles are
  // indexed by the raw cmd byte (O(1), no lock on the request path).
  StatsRegistry registry_;
  struct OpStats {
    std::atomic<int64_t>* count = nullptr;
    std::atomic<int64_t>* errors = nullptr;
    StatHistogram* latency_us = nullptr;
  };
  std::array<OpStats, 256> op_stats_{};
  // Monitor-facing opcode names (kServedOps), indexed by raw cmd byte —
  // shared by the stats registry and span naming.
  std::array<const char*, 256> op_names_{};
  // Span ring behind TRACE_DUMP + the traced-mutation correlator feeding
  // the replication sender.  slow_request_count_ backs the
  // trace.slow_requests registry gauge.
  std::unique_ptr<TraceRing> trace_;
  TraceCorrelator trace_corr_;
  std::atomic<int64_t> slow_request_count_{0};
  // Flight recorder behind EVENT_DUMP + the SIGUSR1 dump (ISSUE 6):
  // structured cluster events from the scrubber, chunk stores,
  // replication sender, ingest sessions, the slow gate, and config
  // anomalies.  Created in Init() before every subsystem that records.
  std::unique_ptr<EventLog> events_;
  // Telemetry history + SLO engine + heat sketch (ISSUE 8): the metrics
  // journal persists one registry snapshot per tick (METRICS_HISTORY),
  // the evaluator turns the same snapshots into slo.breach/recovered
  // flight-recorder events, and the sketch ranks hot file-ids
  // (HEAT_TOP).  Any may be null (conf-disabled).
  std::unique_ptr<MetricsJournal> metrics_;
  std::unique_ptr<SloEvaluator> slo_;
  std::unique_ptr<HeatSketch> heat_;
  // Admission control & request QoS (ISSUE 19; storage/admission.h):
  // consulted at the request-header stage on every nio thread, ticked
  // on the metrics timer from the same snapshots as slo_.
  // inflight_bytes_ is the admitted-but-unanswered request-byte ledger
  // (one of the controller's pressure signals, and the
  // admission.inflight_bytes gauge).
  std::unique_ptr<AdmissionController> admission_;
  std::atomic<int64_t> inflight_bytes_{0};
  // Previous tick's snapshot (main-loop only: the tick timer is the
  // sole reader/writer) — the delta base for SLO readings.
  StatsSnapshot last_tick_snap_;
  bool have_tick_snap_ = false;
  int64_t last_tick_mono_us_ = 0;
  // Per-loop duty cycle (nio.loop_busy_pct.*): the accept/timers loop's
  // busy accumulator plus per-tick deltas for it and every nio loop
  // (main-loop only, like last_tick_snap_).  Index 0 = the main loop,
  // 1 + i = nio_[i].
  std::atomic<int64_t> main_loop_busy_us_{0};
  std::vector<int64_t> loop_busy_last_;
  // Saturation telemetry handles (nio loop lag / dio queue health),
  // pre-registered so the per-iteration hook touches only atomics.
  StatHistogram* hist_nio_lag_ = nullptr;
  std::atomic<int64_t>* ctr_nio_dispatched_ = nullptr;
  StatHistogram* hist_dio_wait_ = nullptr;
  StatHistogram* hist_dio_service_ = nullptr;
  // Outbound peer-RPC latency (all op classes), Observed by the health
  // monitor on every successful NetRpc — the peer_rpc_p99_ms SLO input.
  StatHistogram* hist_peer_rpc_ = nullptr;
  StatHistogram* hist_upload_bytes_ = nullptr;
  StatHistogram* hist_download_bytes_ = nullptr;
  std::atomic<int64_t>* ctr_sync_bytes_saved_wire_ = nullptr;
  std::atomic<int64_t>* ctr_sync_digest_mismatch_ = nullptr;
  std::atomic<int64_t>* ctr_chunkfetch_batches_ = nullptr;
  std::atomic<int64_t>* ctr_chunkfetch_chunks_ = nullptr;
  std::atomic<int64_t>* ctr_chunkfetch_bytes_ = nullptr;
  std::atomic<int64_t>* ctr_recv_hashed_bytes_ = nullptr;
  std::atomic<int64_t>* ctr_fallback_rehash_ = nullptr;
  // dedup.chunk_hits / chunk_misses, and erasure coding on write
  // (IngestFor): stripes committed by uploads, their data shards' bytes
  // (k x shard_len, what the sidecar's ec_encode_bytes counts when it
  // computed the parity), and stripes the sidecar did not encode (each
  // upload then stored flat).
  IngestCounters ingest_ctrs_;
  // Negotiated-upload (ingest edge) accounting: completed recipe
  // uploads, chunk bytes the client did NOT ship because the store
  // already held them, and server-observable fallbacks (no chunk
  // store, failed/expired sessions — the client then re-sends via
  // plain UPLOAD_FILE).
  std::atomic<int64_t>* ctr_ingest_recipe_uploads_ = nullptr;
  std::atomic<int64_t>* ctr_ingest_bytes_saved_wire_ = nullptr;
  std::atomic<int64_t>* ctr_ingest_fallbacks_ = nullptr;
  std::atomic<int64_t>* ctr_ingest_chunks_present_ = nullptr;
  std::atomic<int64_t>* ctr_ingest_chunks_shipped_ = nullptr;
  // Negotiated-upload stage clocks (the access log's last five columns).
  StatHistogram* hist_ingest_negotiate_ = nullptr;
  StatHistogram* hist_ingest_present_ = nullptr;
  StatHistogram* hist_ingest_verify_ = nullptr;
  StatHistogram* hist_ingest_reindex_ = nullptr;
  // Ranged downloads (the parallel client splits a file into ranges):
  // requests with a nonzero offset or an explicit byte count, and the
  // bytes they actually served.
  std::atomic<int64_t>* ctr_download_ranged_requests_ = nullptr;
  std::atomic<int64_t>* ctr_download_ranged_bytes_ = nullptr;
  // Vectored cold-span reads (ISSUE 18): per RecipeStream refill round
  // the slab-resident cold spans batch into one preadv per (slab file,
  // contiguous run).  spans > batches is the syscall-reduction proof on
  // a chunked corpus; per-span pread fallbacks don't count here.
  std::atomic<int64_t>* ctr_dio_preadv_batches_ = nullptr;
  std::atomic<int64_t>* ctr_dio_preadv_spans_ = nullptr;
  // Parked phase-1 sessions keyed by id (ingest_mu_); swept by timer.
  RankedMutex ingest_mu_{LockRank::kIngestSessions};
  std::unordered_map<int64_t, std::unique_ptr<UploadSession>>
      ingest_sessions_;
  std::atomic<int64_t> next_ingest_session_{1};
  // Local IP as seen by the first accepted connection, published
  // lock-free: with sharded accept ANY reactor thread may capture it
  // while handlers on other threads read it.  State 0 = empty, 1 = a
  // writer owns the string, 2 = set (release-published; readers acquire
  // before touching my_ip_).
  std::string my_ip_;
  std::atomic<int> my_ip_state_{0};

  // Trunk state (cluster-global params from the tracker; SURVEY §2.3).
  // Guarded by trunk_mu_: mutated by the main-loop param timer, read by
  // every nio/dio thread.  Handlers copy the shared_ptr under the lock
  // and use the allocator outside it (the allocator locks internally);
  // the timer swaps the pointer, never mutates a live allocator.
  mutable RankedMutex trunk_mu_{LockRank::kTrunkRole};
  bool trunk_enabled_ = false;
  int64_t slot_min_size_ = 256;
  int64_t slot_max_size_ = 16 * 1024 * 1024;
  int64_t trunk_file_size_ = 64LL * 1024 * 1024;
  std::string trunk_ip_;
  int trunk_port_ = 0;
  int64_t trunk_epoch_ = 0;  // fencing token (see trunk.h RPC note)
  bool is_trunk_server_ = false;
  // Role-regain safety: after losing and regaining the trunk role, hold
  // this many seconds before rescanning (interim allocations may still be
  // replicating in); see RefreshClusterParams.
  static constexpr int kTrunkRegainGraceS = 3;
  bool held_trunk_role_before_ = false;
  int64_t trunk_regain_not_before_ = 0;
  bool trunk_size_err_logged_ = false;
  std::shared_ptr<TrunkAllocator> trunk_alloc_;
  FILE* access_log_ = nullptr;
  std::string stat_path_;
};

}  // namespace fdfs
