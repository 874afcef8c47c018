// Byte-level codecs shared by the daemons: big-endian int64 framing,
// URL-safe base64 (file-ID alphabet), CRC32, SHA1.
//
// Reference equivalents: libfastcommon shared_func.c (long2buff/buff2long),
// base64.c (file-ID codec), hash.c CRC32, md5.c/sha1 analogues.  Must stay
// bit-compatible with fastdfs_tpu/common (cross-checked by
// tests/test_native_common.py golden vectors).
#pragma once

#include <cstddef>
#include <cstdint>
#include <string>
#include <string_view>

namespace fdfs {

// -- fixed-width NUL-padded string fields (group/ip wire fields) ----------
void PutFixedField(std::string* out, std::string_view s, size_t width);
std::string GetFixedField(const uint8_t* p, size_t width);

// -- endian framing (reference: shared_func.c long2buff/buff2long) --------
void PutInt64BE(int64_t v, uint8_t* out);
int64_t GetInt64BE(const uint8_t* in);
void PutInt32BE(uint32_t v, uint8_t* out);
uint32_t GetInt32BE(const uint8_t* in);
void PutInt16BE(uint16_t v, uint8_t* out);
uint16_t GetInt16BE(const uint8_t* in);

// -- URL-safe base64, no padding (file-ID codec; 20 bytes -> 27 chars) ----
std::string Base64UrlEncode(const uint8_t* data, size_t len);
// Returns false on invalid input characters or impossible length.
bool Base64UrlDecode(std::string_view s, std::string* out);

// -- CRC32 (IEEE, zlib-compatible; reference: hash.c crc32) ---------------
// `seed` chains: Crc32(b, nb, Crc32(a, na)) == Crc32(a || b).
uint32_t Crc32(const void* data, size_t len, uint32_t seed = 0);
// The loop Crc32 runs, fixed at first use by what the CPU has (STAT gauge
// `crc32.impl`), and the same sum by a named loop, for tests.  kFolded may
// be asked for only where Crc32Chosen() returns it.
enum class Crc32Impl { kSliced = 0, kFolded = 1 };
Crc32Impl Crc32Chosen();
uint32_t Crc32With(Crc32Impl impl, const void* data, size_t len,
                   uint32_t seed = 0);

// -- JSON string escaping (every hand-built wire-JSON emitter: STAT /
// EVENT_DUMP / METRICS_HISTORY / HEAT_TOP).  Appends `s` quoted, with
// ", \, \n, \r, \t escaped and other control bytes as \u00XX — one
// definition so an escaping fix can never miss a wire surface.
void AppendJsonString(std::string* out, std::string_view s);

// Raw bytes -> lowercase hex (digest wire/display form).
std::string BytesToHex(const uint8_t* data, size_t len);
// Lowercase/uppercase hex -> raw bytes appended to *out; false on odd
// length or non-hex characters (nothing appended then).
bool HexToBytes(std::string_view hex, std::string* out);

// -- SHA1 (dedup CPU baseline path) ---------------------------------------
struct Sha1Digest {
  uint8_t bytes[20];
  std::string Hex() const;
};
Sha1Digest Sha1(const void* data, size_t len);

// Incremental SHA1 for streamed uploads (chunked dio writes).
class Sha1Stream {
 public:
  Sha1Stream();
  void Update(const void* data, size_t len);
  Sha1Digest Final();

 private:
  uint32_t h_[5];
  uint64_t total_;
  uint8_t buf_[64];
  size_t buf_len_;
};

}  // namespace fdfs
