// Disk recovery: rebuild a wiped/replaced store path from a group peer.
//
// Reference: storage/storage_disk_recovery.c —
// storage_disk_recovery_start() fetches the one-path binlog from a peer
// (STORAGE_PROTO_CMD_FETCH_ONE_PATH_BINLOG) and re-downloads every file it
// lists; the recovering server is held out of read routing (status
// RECOVERY upstream; WAIT_SYNC/SYNCING here via the tracker's re-enter-
// sync handshake) until it declares done.
//
// Honest divergences: upstream restores CREATE_LINK files as links; the
// rebuild re-downloads the content (a full copy — correct bytes, more
// space).  Metadata sidecars are restored via GET_METADATA from the peer.
// Beyond upstream: recipe-stored files rebuild CHUNK-AWARE (FETCH_RECIPE
// + FETCH_CHUNK pull only the chunk bytes the local store lacks), so a
// dup-heavy path costs ~unique bytes of wire instead of every logical
// byte; any failure falls back per-file to the full download.
#pragma once

#include <atomic>
#include <cstdint>
#include <functional>
#include <string>
#include <thread>
#include <vector>

#include "common/trace.h"
#include "storage/chunkstore.h"
#include "storage/config.h"
#include "storage/store.h"
#include "storage/tracker_client.h"

namespace fdfs {

class RecoveryManager {
 public:
  RecoveryManager(const StorageConfig& cfg, TrackerReporter* reporter,
                  StoreManager* store);
  ~RecoveryManager();

  // Whether recovery is needed: a store path was freshly (re-)initialized
  // although this server had previously joined a group (sync marks
  // exist), or a prior recovery never finished (.recovery marker).
  // Decided BEFORE the reporter joins so the JOIN can carry the
  // recovering flag (the node must never pass through ACTIVE with a
  // wiped disk).
  bool NeedsRecovery(bool data_was_fresh) const;
  // Chunk-dedup parity: recovered files at or above `threshold` bytes are
  // routed through the server's chunk store exactly like uploaded/synced
  // ones (fn(tmp_path, spi, size, remote) -> stored?).  Unset or failing
  // hook falls back to the flat rename.
  using ChunkedStoreFn = std::function<bool(
      const std::string& tmp_path, int spi, int64_t size,
      const std::string& remote)>;
  void SetChunkedStore(ChunkedStoreFn fn, int64_t threshold) {
    chunked_store_ = std::move(fn);
    chunk_threshold_ = threshold;
  }

  // Chunk-aware recovery: materialize `recipe` for `remote` on store
  // path `spi`, taking refs on chunks already present locally and
  // calling `fetch_chunks(want, out)` — one BATCHED peer round-trip
  // returning the payloads concatenated in `want` order — for the
  // rest.  Returns false on any failure — the caller then falls back
  // to the full-file download.  Dup-heavy rebuilds move only unique
  // bytes over the wire this way.
  using FetchChunksFn = std::function<bool(
      const std::vector<RecipeEntry>& want, std::string* out)>;
  // The hook reports *chunks_fetched (pulled over the wire) and
  // *chunks_local (satisfied by refs on chunks this node already held)
  // so the recovery counters reflect wire traffic, not recipe sizes
  // (the old accounting charged every chunk of every recovered recipe
  // as "pulled").
  using RecipeRecoverFn = std::function<bool(
      int spi, const std::string& remote, const Recipe& recipe,
      const FetchChunksFn& fetch_chunks, int64_t* chunks_fetched,
      int64_t* chunks_local)>;
  void SetRecipeRecover(RecipeRecoverFn fn) {
    recipe_recover_ = std::move(fn);
  }

  // Distributed tracing: each recovered file becomes one trace
  // ("recovery.file" root + per-fetch child spans), its context
  // prefixed onto the peer RPCs so the serving node's FETCH_RECIPE /
  // FETCH_CHUNK / DOWNLOAD spans stitch cross-node.  null = untraced.
  void SetTrace(TraceRing* ring) { trace_ = ring; }

  // Start the background rebuild (call only when NeedsRecovery).
  void Start();
  void Stop();
  bool running() const { return running_; }
  int64_t files_recovered() const { return files_recovered_; }
  int64_t files_skipped() const { return files_skipped_; }
  int64_t chunks_pulled() const { return chunks_pulled_; }
  int64_t chunks_local() const { return chunks_local_; }

 private:
  struct TrackerReply {
    bool reached = false;
    uint8_t status = 0;
    std::string body;
  };
  void ThreadMain();
  // One RPC against every configured tracker (each holds independent
  // sync state for this node).
  std::vector<TrackerReply> TrackerRpcAll(uint8_t cmd,
                                          const std::string& body);
  // Marker phase record: "fetch" while data is being rebuilt, "notify"
  // once complete but with done-notify acks still outstanding.
  std::string ReadMarkerPhase() const;
  void WriteMarkerPhase(const std::string& phase) const;
  // Retry the done-notify against every tracker until each acks (or
  // shutdown); returns true when all acked.
  bool NotifyAllTrackers(const std::string& self);
  bool RecoverPath(const PeerInfo& peer, int spi);
  // All peer RPCs reuse one keepalive connection (*fd, -1 = closed);
  // callees reconnect once on IO failure.  Millions of small files would
  // otherwise pay a TCP handshake per file (twice, with metadata).
  bool EnsurePeerConn(const PeerInfo& peer, int* fd);
  bool FetchOnePathBinlog(const PeerInfo& peer, int* fd, int spi,
                          std::string* lines);
  bool DownloadToFile(const PeerInfo& peer, int* fd,
                      const std::string& remote,
                      const std::string& dest_path, bool* missing);
  bool FetchMetadata(const PeerInfo& peer, int* fd, const std::string& remote,
                     std::string* meta);
  bool StoreRecovered(const std::string& remote, const std::string& tmp_path);
  // Chunk-aware pulls (FETCH_RECIPE / FETCH_CHUNK).  FetchRecipe returns
  // false on transport failure; *flat = true when the peer stores the
  // file flat (ENOENT) — download normally then.
  bool FetchRecipe(const PeerInfo& peer, int* fd, const std::string& remote,
                   Recipe* recipe, bool* flat);
  bool FetchChunks(const PeerInfo& peer, int* fd, const std::string& remote,
                   const std::vector<RecipeEntry>& want, std::string* out);
  // TRACE_CTX prefix frame for the next peer RPC (no-op when the
  // current file is untraced); false = transport failure.
  bool SendTracePrefix(int fd);
  // Record a child span of the current file's trace (no-op untraced).
  void RecordFetchSpan(const char* name, int64_t start_us, bool ok);
  // Close (record) the current file's root span and clear the context.
  void CloseFileTrace(int64_t start_us, bool ok);

  StorageConfig cfg_;
  TrackerReporter* reporter_;
  StoreManager* store_;
  std::string marker_path_;
  std::thread thread_;
  std::atomic<bool> stop_{false};
  std::atomic<bool> running_{false};
  std::atomic<int64_t> files_recovered_{0};
  std::atomic<int64_t> files_skipped_{0};
  std::atomic<int64_t> chunks_pulled_{0};  // fetched over the wire
  std::atomic<int64_t> chunks_local_{0};   // satisfied by local refs
  ChunkedStoreFn chunked_store_;
  RecipeRecoverFn recipe_recover_;
  int64_t chunk_threshold_ = 0;
  // Recovery runs on ONE thread, so the current file's trace context
  // needs no locking; parent_span holds the file's root span id.
  TraceRing* trace_ = nullptr;
  TraceCtx cur_trace_;
};

}  // namespace fdfs
