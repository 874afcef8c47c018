// Storage daemon configuration (reference: conf/storage.conf parsed by
// storage/storage_func.c:storage_load_from_conf_file()).
#pragma once

#include <string>
#include <vector>

#include "common/cdc.h"  // CdcWidths
#include "common/ini.h"

namespace fdfs {

struct StorageConfig {
  std::string group_name = "group1";
  std::string bind_addr;           // empty = all interfaces
  int port = 23000;
  std::string base_path;           // logs, stat, sync state
  std::vector<std::string> store_paths;  // store_path0..N (data roots)
  // Pre-created data-dir fan-out per store path.  NOTE: the subdir *spread*
  // inside file IDs is a protocol constant (always mod 256, see
  // common/fileid.h) so clients can validate IDs without knowing server
  // config; this knob only controls how much of the fan-out Init
  // pre-creates (the rest is mkdir'd lazily).
  int subdir_count_per_path = 256;
  int buff_size = 256 * 1024;      // chunked IO size
  int network_timeout_ms = 30000;
  // nio work threads (reference storage.conf:work_threads /
  // storage_nio.c): connections are distributed round-robin over this
  // many event loops.  Init() always spawns this many dedicated nio
  // threads (with 1, all connections share one nio thread; the main
  // loop only accepts).
  int work_threads = 4;
  // Sharded accept (ISSUE 18): each nio loop binds its own SO_REUSEPORT
  // listening socket and owns every connection it accepts — no
  // cross-loop handoff, accept pressure spread by the kernel.  When the
  // kernel refuses the option the daemon falls back to the single
  // main-loop acceptor with round-robin handoff (an anomaly notes the
  // fallback).  0 disables sharding outright.
  bool nio_reuseport = true;
  // dio pool size PER STORE PATH (reference storage.conf:
  // disk_writer_threads / storage_dio.c): chunk-store writes,
  // fingerprint RPCs, trunk allocation, and deletes run here.  0 = the
  // daemon derives it at start from the host's cores and the number of
  // store paths (common/workers.h:DioWorkersPerPath).
  int disk_writer_threads = 0;
  // Accept-time connection cap (reference storage.conf:max_connections /
  // fast_task_queue.c — the task-buffer pool is the bound upstream; here
  // the cap is explicit).  Past the cap the daemon answers one EBUSY
  // response header and closes — a polite refusal the client surfaces as
  // a status error instead of ECONNRESET.  0 = unlimited.
  int max_connections = 256;
  std::vector<std::string> tracker_servers;  // "ip:port"
  int heart_beat_interval_s = 30;
  int stat_report_interval_s = 60;
  int sync_interval_ms = 100;      // binlog tail poll when idle
  std::string dedup_mode = "none"; // none | cpu | sidecar
  std::string dedup_sidecar;       // unix socket path when mode=sidecar
  // Chunk-level dedup threshold: uploads >= this many bytes are CDC-
  // chunked into the content-addressed chunk store (recipe file on disk);
  // smaller files use whole-file dedup.  0 disables chunking.
  int64_t dedup_chunk_threshold = 64 * 1024;
  // Segment size for streaming fingerprint RPCs (CDC restarts per
  // segment so a multi-GB upload never needs a contiguous buffer).
  int64_t dedup_segment_bytes = 64LL * 1024 * 1024;
  // dedup_cdc_widths = <min>:<avg_bits>:<max> (sizes take K/M suffixes):
  // the chunker's three widths, one key because they are one choice.
  // Both plugins cut with them and a sidecar at other widths is refused
  // (dedup.h).  Load() rejects min < 32 (the gear window), min >= max,
  // max > dedup_segment_bytes, avg_bits outside [1, 31].
  CdcWidths cdc_widths;
  // Negotiated-upload session lifetime: a client that sent
  // UPLOAD_RECIPE but never completed UPLOAD_CHUNKS holds pins on the
  // chunks its bitmap reported present; the sweep timer aborts (and
  // unpins) sessions older than this, so a vanished client can never
  // leak pins.  Must cover the client's think time between the two
  // requests plus one payload upload.
  int upload_session_timeout_s = 30;
  std::string log_level = "info";
  // Optional file sink (empty = stderr) with size/day rotation
  // (reference: logger.c; base_path-relative paths allowed).
  std::string log_file;
  int64_t log_rotate_size = 256LL << 20;
  // Per-request access log (storage.conf:use_access_log): op, client ip,
  // status, bytes, cost in µs — logs/access.log.
  bool use_access_log = false;
  // Distributed tracing (common/trace.h): capacity of the span ring
  // buffer dumped via StorageCmd::TRACE_DUMP, and the slow-request
  // threshold — a request slower than this is span-retained even when
  // untraced and logged as one structured JSON line.  0 disables the
  // slow gate (traced requests still record).
  int trace_buffer_size = 4096;
  int64_t slow_request_threshold_ms = 1000;
  // Integrity engine (storage/scrub.h).  scrub_interval_s: cadence of
  // the background verify+repair+GC pass (0 = no periodic passes;
  // SCRUB_KICK still forces one).  scrub_bandwidth_mb_s: verify read
  // pace so scrubbing never starves foreground IO (0 = unlimited).
  // chunk_gc_grace_s: how long a zero-ref chunk's bytes stay on disk
  // before a GC pass may reclaim them (0 = unlink eagerly on delete,
  // the pre-scrubber behavior).
  int scrub_interval_s = 86400;
  int scrub_bandwidth_mb_s = 0;
  int64_t chunk_gc_grace_s = 0;
  // Slab packing (storage/slabstore.h; OPERATIONS.md "Slab packing &
  // compaction"): chunks below slab_chunk_threshold and encoded
  // recipes below slab_recipe_threshold are appended into
  // slab_size_mb slab files under <store_path>/data/slabs/ instead of
  // per-object inodes — the billion-small-files layout.  Thresholds of
  // 0 disable packing for that class (both 0 = flat layout only).
  // slab_compact_min_dead_pct: a slab becomes a compaction victim once
  // deletes mark that share of its bytes dead (the scrub pass drives
  // paced compaction).
  int64_t slab_chunk_threshold = 64 * 1024;
  int64_t slab_recipe_threshold = 64 * 1024;
  int slab_size_mb = 64;
  int slab_compact_min_dead_pct = 25;
  // Hot-chunk read cache (per store path): bounded LRU of chunk
  // payloads consulted by DOWNLOAD_FILE / FETCH_CHUNK, invalidated on
  // quarantine and GC unlink (OPERATIONS.md "Read path, caching &
  // parallel downloads").  0 disables it.
  int read_cache_mb = 64;
  // Flight recorder (common/eventlog.h): capacity of the bounded ring
  // of structured cluster events dumped via StorageCmd::EVENT_DUMP and
  // on SIGUSR1 (OPERATIONS.md "Saturation & flight recorder").
  int event_buffer_size = 1024;
  // Telemetry history + SLOs + heat (OPERATIONS.md "Telemetry history,
  // SLOs & heat").  metrics_journal_mb: on-disk cap of the metrics
  // history ring (common/metrog.h) dumped via METRICS_HISTORY; 0
  // disables journaling.  slo_eval_interval_s: cadence of the journal
  // tick AND the SLO rule evaluation (common/sloeval.h); 0 disables
  // both.  slo_rules_file: optional conf/slo.conf-style override of the
  // compiled-in rule table (empty = defaults).  heat_top_k: tracked
  // keys per stripe of the hot-file sketch (common/heatsketch.h)
  // behind HEAT_TOP; 0 disables heat telemetry.
  int metrics_journal_mb = 8;
  int slo_eval_interval_s = 5;
  std::string slo_rules_file;
  int heat_top_k = 32;
  // Erasure-coded cold tier (storage/ecstore.h; OPERATIONS.md
  // "Erasure-coded cold tier").  ec_k/ec_m: RS(k, m) stripe geometry —
  // ec_k = 0 (default) disables demotion entirely (existing stripes
  // still serve, repair, and drain).  ec_demote_age_s: chunk payload
  // mtime age before scrub stage 5 may demote it; 0 = cold at once,
  // erasure coding on write (StorageServer::IngestFor).  ec_bandwidth_mb_s:
  // demote/repair IO pace, a SEPARATE token bucket from
  // scrub_bandwidth_mb_s (0 = unlimited).
  int ec_k = 0;
  int ec_m = 2;
  int64_t ec_demote_age_s = 7 * 86400;
  int ec_bandwidth_mb_s = 0;
  // Sampling-profiler ceiling (common/profiler.h; OPERATIONS.md
  // "Profiling & the thread ledger"): the maximum PROFILE_CTL sampling
  // rate this daemon will arm.  0 (the default) disables the profiler
  // entirely — no signal handler, no slab, PROFILE_CTL answers ENOTSUP.
  int profile_max_hz = 0;
  // Gray-failure health layer (common/healthmon.h; OPERATIONS.md
  // "Health, probes & gray failure").  health_probe_interval_s: cadence
  // of the active probe loop — ACTIVE_TEST pings to the trackers + the
  // group's ACTIVE peers plus a per-store-path disk probe (4 KB
  // tmp-write+fsync, then read back); 0 disables active probing (the
  // passive NetRpc table and watchdog still run).
  // probe_slow_threshold_ms: a disk probe slower than this records a
  // disk.gray flight-recorder event and halves the node's gray score.
  // watchdog_stall_threshold_ms: a registered daemon thread whose
  // heartbeat is older than this is reported stalled (watchdog.stall
  // event + gauge + gray score); 0 disables the watchdog.
  // watchdog_inject_stall_ms: DEBUG — spawn a thread that beats once
  // then sleeps forever, guaranteeing one watchdog trip (tests only).
  int health_probe_interval_s = 30;
  int probe_slow_threshold_ms = 1000;
  int watchdog_stall_threshold_ms = 5000;
  int watchdog_inject_stall_ms = 0;
  // Admission control & request QoS (storage/admission.h; OPERATIONS.md
  // "Overload control & request QoS").  admission_control gates the
  // whole subsystem (requests are still priority-classified and counted
  // when off, but nothing is shed).  The ladder moves one rung per
  // metrics tick when the pressure EWMA crosses admission_tighten_pct /
  // admission_relax_pct (percent of the 1.0 "at the configured limit"
  // score; relax must sit below tighten — that gap is the anti-flap
  // hysteresis band).  The *_high knobs are the normalization points
  // where each raw signal reads as 100% pressure: total dio jobs
  // pending, reactor loop-lag p99, and admitted-but-unanswered request
  // bytes.  admission_retry_after_ms is the base EBUSY backoff hint;
  // the wire carries base x current level.
  bool admission_control = true;
  int admission_tighten_pct = 90;
  int admission_relax_pct = 45;
  int64_t admission_queue_depth_high = 64;
  int64_t admission_loop_lag_high_ms = 100;
  int64_t admission_inflight_high_bytes = 256LL << 20;
  int64_t admission_retry_after_ms = 500;
  // Config values Load() silently clamped or corrected — surfaced as
  // "config.anomaly" flight-recorder events at startup so a daemon
  // running on not-what-the-operator-wrote config is diagnosable.
  std::vector<std::string> anomalies;

  // Parse + validate; false with *error on problems.
  bool Load(const IniConfig& ini, std::string* error);
};

}  // namespace fdfs
