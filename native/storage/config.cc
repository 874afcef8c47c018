#include "storage/config.h"

#include "common/workers.h"  // kDioWorkersCap

namespace fdfs {

namespace {

// "<min>:<avg_bits>:<max>", e.g. "2K:13:64K" or "512K:20:8M".
bool ParseCdcWidths(const std::string& text, CdcWidths* out) {
  const size_t a = text.find(':');
  const size_t b = a == std::string::npos ? a : text.find(':', a + 1);
  if (b == std::string::npos || text.find(':', b + 1) != std::string::npos)
    return false;
  out->min_size = IniConfig::ParseBytes(text.substr(0, a), -1);
  const int64_t bits = IniConfig::ParseBytes(text.substr(a + 1, b - a - 1), -1);
  out->max_size = IniConfig::ParseBytes(text.substr(b + 1), -1);
  out->avg_bits = static_cast<int>(bits);
  return out->min_size >= 0 && bits >= 0 && bits < 64 && out->max_size >= 0;
}

}  // namespace

bool StorageConfig::Load(const IniConfig& ini, std::string* error) {
  anomalies.clear();
  auto note = [this](const std::string& what) { anomalies.push_back(what); };
  group_name = ini.GetStr("group_name", group_name);
  bind_addr = ini.GetStr("bind_addr", "");
  port = static_cast<int>(ini.GetInt("port", port));
  base_path = ini.GetStr("base_path", "");
  if (base_path.empty()) {
    *error = "base_path is required";
    return false;
  }
  store_paths.clear();
  int n = static_cast<int>(ini.GetInt("store_path_count", 0));
  if (n == 0) {
    // Upstream default: store_path0 defaults to base_path.
    auto sp0 = ini.Get("store_path0");
    store_paths.push_back(sp0.has_value() && !sp0->empty() ? *sp0 : base_path);
  } else {
    for (int i = 0; i < n; ++i) {
      auto v = ini.Get("store_path" + std::to_string(i));
      if (!v.has_value() || v->empty()) {
        *error = "store_path" + std::to_string(i) + " missing";
        return false;
      }
      store_paths.push_back(*v);
    }
  }
  if (store_paths.size() > 256) {
    *error = "too many store paths (max 256)";
    return false;
  }
  subdir_count_per_path =
      static_cast<int>(ini.GetInt("subdir_count_per_path", subdir_count_per_path));
  if (subdir_count_per_path < 1 || subdir_count_per_path > 256) {
    *error = "subdir_count_per_path must be in [1,256]";
    return false;
  }
  buff_size = static_cast<int>(ini.GetBytes("buff_size", buff_size));
  network_timeout_ms =
      static_cast<int>(ini.GetSeconds("network_timeout", 30) * 1000);
  tracker_servers = ini.GetAll("tracker_server");
  heart_beat_interval_s =
      static_cast<int>(ini.GetSeconds("heart_beat_interval", 30));
  stat_report_interval_s =
      static_cast<int>(ini.GetSeconds("stat_report_interval", 60));
  sync_interval_ms = static_cast<int>(ini.GetInt("sync_interval_ms", 100));
  work_threads = static_cast<int>(ini.GetInt("work_threads", work_threads));
  if (work_threads < 1) work_threads = 1;
  if (work_threads > 64) {
    note("work_threads clamped to 64");
    work_threads = 64;
  }
  nio_reuseport = ini.GetBool("nio_reuseport", nio_reuseport);
  disk_writer_threads = static_cast<int>(
      ini.GetInt("disk_writer_threads", disk_writer_threads));
  // 0 = derived at start from the host's cores and the store paths
  // (workers.h:DioWorkersPerPath); a positive value pins it.
  if (disk_writer_threads < 0) disk_writer_threads = 0;
  if (disk_writer_threads > kDioWorkersCap) {
    note("disk_writer_threads clamped to " + std::to_string(kDioWorkersCap));
    disk_writer_threads = kDioWorkersCap;
  }
  max_connections =
      static_cast<int>(ini.GetInt("max_connections", max_connections));
  if (max_connections < 0) max_connections = 0;
  dedup_mode = ini.GetStr("dedup_mode", "none");
  if (dedup_mode != "none" && dedup_mode != "cpu" && dedup_mode != "sidecar") {
    *error = "dedup_mode must be none|cpu|sidecar";
    return false;
  }
  dedup_sidecar = ini.GetStr("dedup_sidecar", "");
  dedup_chunk_threshold = ini.GetBytes("dedup_chunk_threshold", 64 * 1024);
  dedup_segment_bytes =
      ini.GetBytes("dedup_segment_bytes", 64LL * 1024 * 1024);
  if (dedup_segment_bytes < (1 << 20)) dedup_segment_bytes = 1 << 20;
  const std::string widths = ini.GetStr("dedup_cdc_widths", "");
  cdc_widths = CdcWidths();
  if (!widths.empty() && !ParseCdcWidths(widths, &cdc_widths)) {
    *error = "dedup_cdc_widths must be <min>:<avg_bits>:<max>, e.g. "
             "2K:13:64K (got '" + widths + "')";
    return false;
  }
  if (cdc_widths.min_size < 32 || cdc_widths.min_size >= cdc_widths.max_size ||
      cdc_widths.max_size > dedup_segment_bytes || cdc_widths.avg_bits < 1 ||
      cdc_widths.avg_bits > 31) {
    *error = "dedup_cdc_widths = " + widths + ": the minimum must be at "
             "least 32 (the gear window) and under the maximum, the maximum "
             "no larger than dedup_segment_bytes, avg_bits in [1,31]";
    return false;
  }
  upload_session_timeout_s = static_cast<int>(
      ini.GetSeconds("upload_session_timeout", upload_session_timeout_s));
  if (upload_session_timeout_s < 1) upload_session_timeout_s = 1;
  log_level = ini.GetStr("log_level", "info");
  log_file = ini.GetStr("log_file", "");
  log_rotate_size = ini.GetBytes("log_rotate_size", log_rotate_size);
  use_access_log = ini.GetBool("use_access_log", false);
  trace_buffer_size =
      static_cast<int>(ini.GetInt("trace_buffer_size", trace_buffer_size));
  if (trace_buffer_size < 16) trace_buffer_size = 16;
  slow_request_threshold_ms =
      ini.GetInt("slow_request_threshold_ms", slow_request_threshold_ms);
  if (slow_request_threshold_ms < 0) slow_request_threshold_ms = 0;
  scrub_interval_s = static_cast<int>(
      ini.GetSeconds("scrub_interval_s", scrub_interval_s));
  if (scrub_interval_s < 0) scrub_interval_s = 0;
  scrub_bandwidth_mb_s = static_cast<int>(
      ini.GetInt("scrub_bandwidth_mb_s", scrub_bandwidth_mb_s));
  if (scrub_bandwidth_mb_s < 0) scrub_bandwidth_mb_s = 0;
  // 1 TB/s cap: keeps the pacing arithmetic far from int64 limits (a
  // larger value is indistinguishable from unpaced anyway).
  if (scrub_bandwidth_mb_s > (1 << 20)) {
    note("scrub_bandwidth_mb_s clamped to 1 TB/s");
    scrub_bandwidth_mb_s = 1 << 20;
  }
  chunk_gc_grace_s = ini.GetSeconds("chunk_gc_grace_s", chunk_gc_grace_s);
  if (chunk_gc_grace_s < 0) chunk_gc_grace_s = 0;
  slab_chunk_threshold =
      ini.GetBytes("slab_chunk_threshold", slab_chunk_threshold);
  if (slab_chunk_threshold < 0) slab_chunk_threshold = 0;
  slab_recipe_threshold =
      ini.GetBytes("slab_recipe_threshold", slab_recipe_threshold);
  if (slab_recipe_threshold < 0) slab_recipe_threshold = 0;
  slab_size_mb = static_cast<int>(ini.GetInt("slab_size_mb", slab_size_mb));
  if (slab_size_mb < 1) {
    note("slab_size_mb raised to 1");
    slab_size_mb = 1;
  }
  // 1 GB cap: compaction rewrites a whole victim slab per pass slice,
  // and a bigger slab only dilutes the dead-share trigger.
  if (slab_size_mb > 1024) {
    note("slab_size_mb clamped to 1024");
    slab_size_mb = 1024;
  }
  // A record must FIT a slab with room to spare or the active slab
  // rolls on every append; cap both thresholds at half the slab.
  int64_t slab_cap = (static_cast<int64_t>(slab_size_mb) << 20) / 2;
  if (slab_chunk_threshold > slab_cap) {
    note("slab_chunk_threshold clamped to slab_size_mb/2");
    slab_chunk_threshold = slab_cap;
  }
  if (slab_recipe_threshold > slab_cap) {
    note("slab_recipe_threshold clamped to slab_size_mb/2");
    slab_recipe_threshold = slab_cap;
  }
  slab_compact_min_dead_pct = static_cast<int>(
      ini.GetInt("slab_compact_min_dead_pct", slab_compact_min_dead_pct));
  if (slab_compact_min_dead_pct < 1) slab_compact_min_dead_pct = 1;
  if (slab_compact_min_dead_pct > 100) slab_compact_min_dead_pct = 100;
  read_cache_mb = static_cast<int>(ini.GetInt("read_cache_mb",
                                              read_cache_mb));
  if (read_cache_mb < 0) read_cache_mb = 0;
  // 64 GB cap: the cache is per store path and RAM-resident.
  if (read_cache_mb > (64 << 10)) {
    note("read_cache_mb clamped to 64 GB");
    read_cache_mb = 64 << 10;
  }
  event_buffer_size = static_cast<int>(
      ini.GetInt("event_buffer_size", event_buffer_size));
  if (event_buffer_size < 16) event_buffer_size = 16;
  if (event_buffer_size > (1 << 20)) {
    note("event_buffer_size clamped to 1M");
    event_buffer_size = 1 << 20;
  }
  metrics_journal_mb = static_cast<int>(
      ini.GetInt("metrics_journal_mb", metrics_journal_mb));
  if (metrics_journal_mb < 0) metrics_journal_mb = 0;
  // METRICS_HISTORY reads both ring files whole before decoding, so the
  // cap is also a transient dump-memory bound (the decode itself is
  // bounded at kMaxDecodedSnapshots full registries regardless of ring
  // size).  256 MB of delta records is weeks of history — far past the
  // point where `--since` windows, not ring depth, limit a post-mortem.
  if (metrics_journal_mb > 256) {
    note("metrics_journal_mb clamped to 256");
    metrics_journal_mb = 256;
  }
  ec_k = static_cast<int>(ini.GetInt("ec_k", ec_k));
  if (ec_k < 0) ec_k = 0;
  // 32 data shards already puts a single chunk read across up to 2 of
  // 32 files; wider stripes only grow the blast radius of a stripe
  // loss without improving the (k+m)/k overhead much past k=16.
  if (ec_k > 32) {
    note("ec_k clamped to 32");
    ec_k = 32;
  }
  ec_m = static_cast<int>(ini.GetInt("ec_m", ec_m));
  if (ec_m < 1) {
    note("ec_m raised to 1");
    ec_m = 1;
  }
  // The Cauchy construction needs k + m <= 256 over GF(2^8); 8 parity
  // shards is beyond any sane durability target at group scale.
  if (ec_m > 8) {
    note("ec_m clamped to 8");
    ec_m = 8;
  }
  ec_demote_age_s = ini.GetSeconds("ec_demote_age_s", ec_demote_age_s);
  if (ec_demote_age_s < 0) ec_demote_age_s = 0;
  ec_bandwidth_mb_s = static_cast<int>(
      ini.GetInt("ec_bandwidth_mb_s", ec_bandwidth_mb_s));
  if (ec_bandwidth_mb_s < 0) ec_bandwidth_mb_s = 0;
  if (ec_bandwidth_mb_s > (1 << 20)) {
    note("ec_bandwidth_mb_s clamped to 1 TB/s");
    ec_bandwidth_mb_s = 1 << 20;
  }
  slo_eval_interval_s = static_cast<int>(
      ini.GetSeconds("slo_eval_interval_s", slo_eval_interval_s));
  if (slo_eval_interval_s < 0) slo_eval_interval_s = 0;
  slo_rules_file = ini.GetStr("slo_rules_file", "");
  profile_max_hz = static_cast<int>(
      ini.GetInt("profile_max_hz", profile_max_hz));
  if (profile_max_hz < 0) profile_max_hz = 0;
  // ITIMER_PROF has ~1ms kernel granularity, so rates past 1000 Hz only
  // add handler overhead without adding samples.
  if (profile_max_hz > 1000) {
    note("profile_max_hz clamped to 1000");
    profile_max_hz = 1000;
  }
  health_probe_interval_s = static_cast<int>(
      ini.GetSeconds("health_probe_interval_s", health_probe_interval_s));
  if (health_probe_interval_s < 0) health_probe_interval_s = 0;
  probe_slow_threshold_ms = static_cast<int>(
      ini.GetInt("probe_slow_threshold_ms", probe_slow_threshold_ms));
  if (probe_slow_threshold_ms < 0) probe_slow_threshold_ms = 0;
  watchdog_stall_threshold_ms = static_cast<int>(
      ini.GetInt("watchdog_stall_threshold_ms", watchdog_stall_threshold_ms));
  if (watchdog_stall_threshold_ms < 0) watchdog_stall_threshold_ms = 0;
  // Sub-second thresholds false-positive on the 1s-bounded idle waits
  // every loop uses between beats.
  if (watchdog_stall_threshold_ms > 0 && watchdog_stall_threshold_ms < 2000) {
    note("watchdog_stall_threshold_ms raised to 2000");
    watchdog_stall_threshold_ms = 2000;
  }
  watchdog_inject_stall_ms = static_cast<int>(
      ini.GetInt("watchdog_inject_stall_ms", watchdog_inject_stall_ms));
  if (watchdog_inject_stall_ms < 0) watchdog_inject_stall_ms = 0;
  admission_control = ini.GetBool("admission_control", admission_control);
  admission_tighten_pct = static_cast<int>(
      ini.GetInt("admission_tighten_pct", admission_tighten_pct));
  admission_relax_pct = static_cast<int>(
      ini.GetInt("admission_relax_pct", admission_relax_pct));
  if (admission_tighten_pct < 1) {
    note("admission_tighten_pct raised to 1");
    admission_tighten_pct = 1;
  }
  // The relax threshold must sit strictly below tighten or the ladder
  // oscillates every tick — the exact flap the hysteresis band exists
  // to forbid (same clamp discipline as sloeval's clear <= threshold).
  if (admission_relax_pct >= admission_tighten_pct) {
    note("admission_relax_pct clamped below admission_tighten_pct");
    admission_relax_pct = admission_tighten_pct / 2;
  }
  if (admission_relax_pct < 0) admission_relax_pct = 0;
  admission_queue_depth_high =
      ini.GetInt("admission_queue_depth_high", admission_queue_depth_high);
  if (admission_queue_depth_high < 0) admission_queue_depth_high = 0;
  admission_loop_lag_high_ms =
      ini.GetInt("admission_loop_lag_high_ms", admission_loop_lag_high_ms);
  if (admission_loop_lag_high_ms < 0) admission_loop_lag_high_ms = 0;
  admission_inflight_high_bytes = ini.GetBytes(
      "admission_inflight_high_bytes", admission_inflight_high_bytes);
  if (admission_inflight_high_bytes < 0) admission_inflight_high_bytes = 0;
  admission_retry_after_ms =
      ini.GetInt("admission_retry_after_ms", admission_retry_after_ms);
  if (admission_retry_after_ms < 1) {
    note("admission_retry_after_ms raised to 1");
    admission_retry_after_ms = 1;
  }
  heat_top_k = static_cast<int>(ini.GetInt("heat_top_k", heat_top_k));
  if (heat_top_k < 0) heat_top_k = 0;
  // heat_top_k is the sketch's PER-STRIPE capacity, and a full stripe
  // evicts by scanning all its entries under the stripe mutex on the
  // request path — 1024 keeps that scan a few µs while still tracking
  // 8K keys per node (8 stripes), 32x the default.
  if (heat_top_k > 1024) {
    note("heat_top_k clamped to 1024");
    heat_top_k = 1024;
  }
  return true;
}

}  // namespace fdfs
