// Content-defined chunking: serial gear rolling hash.
//
// CPU-path twin of fastdfs_tpu/ops/gear_cdc.py (the TPU position-parallel
// formulation).  Cut-points are IDENTICAL to the Python serial reference
// (`chunk_stream_ref`) and — for min_size >= window — to the TPU path, so
// every node in a cluster chunks every byte stream the same way.
// Cross-language equality is enforced by tests/test_chunk_cdc.py via the
// codec CLI.
//
// Reference anchor: this replaces the sequential buff_size loop of
// storage/storage_dio.c:dio_write_file() with content-defined spans.
#pragma once

#include <cstddef>
#include <cstdint>
#include <string>
#include <vector>

#include "common/bytes.h"     // PutInt64BE
#include "common/gear_gen.h"  // kCdcDefault*

namespace fdfs {

// The three chunk widths, one choice: no cut before min_size bytes, a cut
// where the low avg_bits of the gear hash are zero (2^avg_bits bytes
// between candidates), a cut at max_size whatever the hash.  The
// defaults are gear_gen.h's kCdcDefault* (2 KiB / 13 / 64 KiB);
// storage.conf states them as dedup_cdc_widths.  Chunks cut at different
// widths share no digests: a set of widths is a content-address namespace.
struct CdcWidths {
  int64_t min_size = kCdcDefaultMinSize;
  int avg_bits = kCdcDefaultAvgBits;
  int64_t max_size = kCdcDefaultMaxSize;
  bool operator==(const CdcWidths& o) const {
    return min_size == o.min_size && avg_bits == o.avg_bits &&
           max_size == o.max_size;
  }
};

// The cut-selection policy GearChunkStream implements (gear_cdc.py's
// CDC_POLICY_DEFAULT; the daemon's chunker knows no other).
constexpr int kCdcPolicyNative = 1;

// QUERY_CHUNKING response body: how this node cuts, as six big-endian
// int64 slots (protocol.py CHUNKING_FIELDS: min_size, avg_bits, max_size,
// cdc_policy, chunk_threshold, segment_bytes).  A client of the
// negotiated upload cuts with these and nothing of its own.
inline std::string PackChunkingParams(const CdcWidths& w,
                                      int64_t chunk_threshold,
                                      int64_t segment_bytes) {
  const int64_t slots[6] = {w.min_size,       w.avg_bits,      w.max_size,
                            kCdcPolicyNative, chunk_threshold, segment_bytes};
  std::string body(sizeof(slots), '\0');
  for (int i = 0; i < 6; ++i)
    PutInt64BE(slots[i], reinterpret_cast<uint8_t*>(body.data()) + i * 8);
  return body;
}

// Exclusive chunk end offsets for data[0..n) (final offset is n; empty
// input -> empty vector).  Semantics: hash resets at each chunk start; a
// position cuts when chunk size >= min_size and the low avg_bits of the
// gear hash are zero, or unconditionally at max_size.
std::vector<int64_t> GearChunkStream(const uint8_t* data, size_t n,
                                     int64_t min_size, int avg_bits,
                                     int64_t max_size);

// Streaming form: carries the rolling state across Feed() calls so a
// multi-gigabyte upload never needs a contiguous buffer.  Offsets
// returned are absolute within the stream.
class GearChunker {
 public:
  GearChunker(int64_t min_size, int avg_bits, int64_t max_size);

  // Consume a segment; appends any cut offsets found to *cuts.
  void Feed(const uint8_t* data, size_t n, std::vector<int64_t>* cuts);
  // End of stream: appends the final partial-chunk offset, if any.
  void Finish(std::vector<int64_t>* cuts);

 private:
  int64_t min_size_;
  uint32_t mask_;
  int64_t max_size_;
  // For min_size >= the 32-byte gear window, h_ carries the NO-RESET
  // stream hash (the two-phase candidate scan in cdc.cc); below the
  // window it carries the serial per-chunk hash.  The two never mix
  // within one chunker.
  uint32_t h_ = 0;
  int64_t pos_ = 0;       // absolute stream position
  int64_t chunk_start_ = 0;
  std::vector<int64_t> cands_;  // phase-1 scratch, reused across Feeds
};

}  // namespace fdfs
