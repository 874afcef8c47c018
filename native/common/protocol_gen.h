// GENERATED by native/gen_protocol.py from fastdfs_tpu/common/protocol.py
// (via native/protocol_manifest.json) -- do not edit by hand; regenerate
// instead.
#pragma once

#include <cstdint>

namespace fdfs {

constexpr int kGroupNameMaxLen = 16;
constexpr int kIpAddressSize = 16;
constexpr int kFileExtNameMaxLen = 6;
constexpr int kFilePrefixMaxLen = 16;
constexpr int kFilenameBase64Length = 27;
constexpr int kStorageIdMaxSize = 16;
constexpr int kProtoPkgLenSize = 8;
constexpr int kHeaderSize = 10;
constexpr int64_t kMaxInlineBody = 67108864LL;
constexpr int kTraceCtxLen = 16;
constexpr int kPriorityFrameLen = 1;
constexpr char kRecordSeparator = '\x01';
constexpr char kFieldSeparator = '\x02';

// Storage-beat stat blob: kBeatStatCount big-endian int64 slots after the
// identity prefix; slot i is named kBeatStatNames[i] (shared contract with
// fastdfs_tpu.common.protocol.BEAT_STAT_FIELDS — append-only).
constexpr int kBeatStatCount = 33;
inline constexpr const char* kBeatStatNames[kBeatStatCount] = {
  "total_upload",
  "success_upload",
  "total_download",
  "success_download",
  "total_delete",
  "success_delete",
  "total_append",
  "success_append",
  "total_set_meta",
  "success_set_meta",
  "total_get_meta",
  "success_get_meta",
  "total_query",
  "success_query",
  "bytes_uploaded",
  "bytes_downloaded",
  "dedup_hits",
  "dedup_bytes_saved",
  "last_source_update",
  "connections",
  "refused_connections",
  "sync_lag_s",
  "sync_bytes_saved_wire",
  "recovery_chunks_fetched",
  "recovery_chunks_local",
  "recovery_files",
  "fetch_chunk_batches",
  "dedup_chunk_misses",
  "rebalance_files_moved",
  "rebalance_bytes_moved",
  "rebalance_files_pending",
  "rebalance_errors",
  "rebalance_done",
};

// Integrity-engine status blob (StorageCmd::kScrubStatus response):
// kScrubStatCount big-endian int64 slots, slot i named
// kScrubStatNames[i] (shared contract with
// fastdfs_tpu.common.protocol.SCRUB_STAT_FIELDS — append-only).
constexpr int kScrubStatCount = 18;
inline constexpr const char* kScrubStatNames[kScrubStatCount] = {
  "running",
  "passes",
  "pass_chunks_done",
  "pass_chunks_total",
  "chunks_verified",
  "bytes_verified",
  "chunks_corrupt",
  "chunks_repaired",
  "corrupt_unrepairable",
  "quarantined",
  "skipped_pinned",
  "gc_pending_chunks",
  "gc_pending_bytes",
  "chunks_reclaimed",
  "bytes_reclaimed",
  "recipes_reclaimed",
  "last_pass_unix",
  "last_pass_duration_us",
};

// Erasure-coding status blob (StorageCmd::kEcStatus response):
// kEcStatCount big-endian int64 slots, slot i named kEcStatNames[i]
// (shared contract with fastdfs_tpu.common.protocol.EC_STAT_FIELDS —
// append-only).
constexpr int kEcStatCount = 16;
inline constexpr const char* kEcStatNames[kEcStatCount] = {
  "enabled",
  "k",
  "m",
  "stripes",
  "stripe_chunks",
  "data_bytes",
  "parity_bytes",
  "demoted_chunks",
  "demoted_bytes",
  "released_chunks",
  "released_bytes",
  "reconstructed_shards",
  "reconstructed_bytes",
  "repair_fallback_chunks",
  "remote_reads",
  "last_demote_unix",
};

enum class TrackerCmd : uint8_t {
  kStorageJoin = 81,
  kQuit = 82,
  kStorageBeat = 83,
  kStorageReportDiskUsage = 84,
  kStorageReplicaChg = 85,
  kStorageSyncSrcReq = 86,
  kStorageSyncDestReq = 87,
  kStorageSyncNotify = 88,
  kStorageSyncReport = 89,
  kStorageSyncDestQuery = 79,
  kStorageReportIpChanged = 78,
  kStorageChangelogReq = 77,
  kStorageParameterReq = 76,
  kServerListOneGroup = 90,
  kServerListAllGroups = 91,
  kServerListStorage = 92,
  kServerDeleteStorage = 93,
  kServerSetTrunkServer = 94,
  kServerClusterStat = 95,
  kTraceDump = 96,
  kStat = 97,
  kEventDump = 98,
  kMetricsHistory = 99,
  kServiceQueryStoreWithoutGroupOne = 101,
  kServiceQueryFetchOne = 102,
  kServiceQueryUpdate = 103,
  kServiceQueryStoreWithGroupOne = 104,
  kServiceQueryFetchAll = 105,
  kServiceQueryStoreWithoutGroupAll = 106,
  kServiceQueryStoreWithGroupAll = 107,
  kResp = 100,
  kActiveTest = 111,
  kTrackerGetStatus = 70,
  kTrackerGetSysFilesStart = 61,
  kTrackerGetSysFilesEnd = 62,
  kTrackerGetOneSysFile = 63,
  kTrackerPingLeader = 71,
  kTrackerNotifyNextLeader = 72,
  kTrackerCommitNextLeader = 73,
  kTrackerGetTrunkServer = 74,
  kQueryPlacement = 64,
  kGroupDrain = 65,
  kGroupReactivate = 66,
  kProfileCtl = 67,
  kProfileDump = 68,
  kHealthMatrix = 69,
  kQueryHotMap = 75,
  kHotFanoutDone = 80,
  kTraceCtx = 140,
  kPriority = 147,
  kAdmissionStatus = 148,
};

enum class StorageCmd : uint8_t {
  kUploadFile = 11,
  kDeleteFile = 12,
  kSetMetadata = 13,
  kDownloadFile = 14,
  kGetMetadata = 15,
  kSyncCreateFile = 16,
  kSyncDeleteFile = 17,
  kSyncUpdateFile = 18,
  kSyncCreateLink = 19,
  kCreateLink = 20,
  kUploadSlaveFile = 21,
  kQueryFileInfo = 22,
  kUploadAppenderFile = 23,
  kAppendFile = 24,
  kSyncAppendFile = 25,
  kFetchOnePathBinlog = 26,
  kTrunkAllocSpace = 27,
  kTrunkAllocConfirm = 28,
  kTrunkFreeSpace = 29,
  kModifyFile = 34,
  kSyncModifyFile = 35,
  kTruncateFile = 36,
  kSyncTruncateFile = 37,
  kDedupFingerprint = 120,
  kDedupQuery = 121,
  kDedupCommit = 122,
  kDedupNeardups = 123,
  kDedupFingerprintCuts = 125,
  kSyncQueryChunks = 126,
  kSyncCreateRecipe = 127,
  kFetchRecipe = 128,
  kFetchChunk = 129,
  kStat = 130,
  kTraceDump = 131,
  kUploadRecipe = 132,
  kUploadChunks = 133,
  kScrubStatus = 134,
  kScrubKick = 135,
  kDedupVerify = 136,
  kEventDump = 137,
  kMetricsHistory = 138,
  kHeatTop = 139,
  kTraceCtx = 140,
  kNearDups = 124,
  kProfileCtl = 141,
  kProfileDump = 142,
  kEcStatus = 143,
  kEcKick = 144,
  kEcRelease = 145,
  kHealthStatus = 146,
  kPriority = 147,
  kAdmissionStatus = 148,
  kQueryChunking = 149,
  kResp = 100,
  kActiveTest = 111,
};

enum class StorageStatus : uint8_t {
  kInit = 0,
  kWaitSync = 1,
  kSyncing = 2,
  kIpChanged = 3,
  kDeleted = 4,
  kOffline = 5,
  kOnline = 6,
  kActive = 7,
  kRecovery = 9,
  kNone = 99,
};

}  // namespace fdfs
