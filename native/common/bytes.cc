#include "common/bytes.h"

#include <algorithm>
#include <array>
#include <cstring>

#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace fdfs {

void PutFixedField(std::string* out, std::string_view s, size_t width) {
  std::string f(width, '\0');
  std::memcpy(f.data(), s.data(), std::min(s.size(), width - 1));
  *out += f;
}

std::string GetFixedField(const uint8_t* p, size_t width) {
  size_t n = 0;
  while (n < width && p[n] != 0) ++n;
  return std::string(reinterpret_cast<const char*>(p), n);
}

void PutInt64BE(int64_t v, uint8_t* out) {
  uint64_t u = static_cast<uint64_t>(v);
  for (int i = 7; i >= 0; --i) {
    out[i] = static_cast<uint8_t>(u & 0xFF);
    u >>= 8;
  }
}

int64_t GetInt64BE(const uint8_t* in) {
  uint64_t u = 0;
  for (int i = 0; i < 8; ++i) u = (u << 8) | in[i];
  return static_cast<int64_t>(u);
}

void PutInt32BE(uint32_t v, uint8_t* out) {
  out[0] = static_cast<uint8_t>(v >> 24);
  out[1] = static_cast<uint8_t>(v >> 16);
  out[2] = static_cast<uint8_t>(v >> 8);
  out[3] = static_cast<uint8_t>(v);
}

void PutInt16BE(uint16_t v, uint8_t* out) {
  out[0] = static_cast<uint8_t>(v >> 8);
  out[1] = static_cast<uint8_t>(v);
}

uint16_t GetInt16BE(const uint8_t* in) {
  return static_cast<uint16_t>((static_cast<uint16_t>(in[0]) << 8) | in[1]);
}

uint32_t GetInt32BE(const uint8_t* in) {
  return (static_cast<uint32_t>(in[0]) << 24) |
         (static_cast<uint32_t>(in[1]) << 16) |
         (static_cast<uint32_t>(in[2]) << 8) | in[3];
}

// -- base64url ------------------------------------------------------------

static const char kB64Alphabet[] =
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_";

std::string Base64UrlEncode(const uint8_t* data, size_t len) {
  std::string out;
  out.reserve((len * 4 + 2) / 3);
  size_t i = 0;
  for (; i + 3 <= len; i += 3) {
    uint32_t v = (data[i] << 16) | (data[i + 1] << 8) | data[i + 2];
    out.push_back(kB64Alphabet[(v >> 18) & 63]);
    out.push_back(kB64Alphabet[(v >> 12) & 63]);
    out.push_back(kB64Alphabet[(v >> 6) & 63]);
    out.push_back(kB64Alphabet[v & 63]);
  }
  size_t rem = len - i;
  if (rem == 1) {
    uint32_t v = data[i] << 16;
    out.push_back(kB64Alphabet[(v >> 18) & 63]);
    out.push_back(kB64Alphabet[(v >> 12) & 63]);
  } else if (rem == 2) {
    uint32_t v = (data[i] << 16) | (data[i + 1] << 8);
    out.push_back(kB64Alphabet[(v >> 18) & 63]);
    out.push_back(kB64Alphabet[(v >> 12) & 63]);
    out.push_back(kB64Alphabet[(v >> 6) & 63]);
  }
  return out;
}

static std::array<int8_t, 256> BuildB64Rev() {
  std::array<int8_t, 256> rev;
  rev.fill(-1);
  for (int i = 0; i < 64; ++i) rev[static_cast<uint8_t>(kB64Alphabet[i])] = i;
  return rev;
}

bool Base64UrlDecode(std::string_view s, std::string* out) {
  static const std::array<int8_t, 256> rev = BuildB64Rev();
  if (s.size() % 4 == 1) return false;  // impossible length
  out->clear();
  out->reserve(s.size() * 3 / 4);
  uint32_t acc = 0;
  int bits = 0;
  for (char c : s) {
    int8_t v = rev[static_cast<uint8_t>(c)];
    if (v < 0) return false;
    acc = (acc << 6) | static_cast<uint32_t>(v);
    bits += 6;
    if (bits >= 8) {
      bits -= 8;
      out->push_back(static_cast<char>((acc >> bits) & 0xFF));
    }
  }
  return true;
}

// -- crc32 (IEEE reflected, zlib-compatible) ---------------------------------
//
// The receive stage runs this over every uploaded byte on an nio thread,
// the slab over every chunk it writes or reads.  Two loops give one
// value: slicing-by-8 (eight table look-ups per eight bytes instead of
// one per byte, portable), and, where cpuid shows PCLMULQDQ, folding 64
// bytes a step by carry-less multiply (Gopal et al., "Fast CRC
// Computation for Generic Polynomials Using PCLMULQDQ Instruction",
// Intel 2009).  The build has no -march, so the folded loop carries a
// target attribute and is chosen once, at start.

namespace {

struct CrcTables {
  uint32_t t[8][256] = {};
  constexpr CrcTables() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[0][i] = c;
    }
    // t[k][i]: the register after byte i and then k zero bytes.
    for (uint32_t i = 0; i < 256; ++i)
      for (int k = 1; k < 8; ++k)
        t[k][i] = t[0][t[k - 1][i] & 0xFF] ^ (t[k - 1][i] >> 8);
  }
};
constexpr CrcTables kCrc;

// Both loops work on the raw register (no inversion at either end).
uint32_t CrcSliced(const uint8_t* p, size_t len, uint32_t c) {
#if __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
  for (; len >= 8; p += 8, len -= 8) {
    uint64_t v;
    std::memcpy(&v, p, 8);
    v ^= c;
    c = kCrc.t[7][v & 0xFF] ^ kCrc.t[6][(v >> 8) & 0xFF] ^
        kCrc.t[5][(v >> 16) & 0xFF] ^ kCrc.t[4][(v >> 24) & 0xFF] ^
        kCrc.t[3][(v >> 32) & 0xFF] ^ kCrc.t[2][(v >> 40) & 0xFF] ^
        kCrc.t[1][(v >> 48) & 0xFF] ^ kCrc.t[0][v >> 56];
  }
#endif
  for (; len > 0; ++p, --len) c = kCrc.t[0][(c ^ *p) & 0xFF] ^ (c >> 8);
  return c;
}

#if defined(__x86_64__)
// x^n mod P for the distances folded over, bit-reflected and shifted
// left once, as the instruction wants them: 64 bytes (n = 512 +- 32)
// and 16 bytes (n = 128 +- 32).
constexpr uint64_t kFold64Lo = 0x154442bd4, kFold64Hi = 0x1c6e41596;
constexpr uint64_t kFold16Lo = 0x1751997d0, kFold16Hi = 0x0ccaa009e;

__attribute__((target("pclmul"))) inline __m128i FoldInto(__m128i acc,
                                                           __m128i k,
                                                           __m128i next) {
  __m128i lo = _mm_clmulepi64_si128(acc, k, 0x00);
  __m128i hi = _mm_clmulepi64_si128(acc, k, 0x11);
  return _mm_xor_si128(_mm_xor_si128(lo, hi), next);
}

// `len` >= 64 and a multiple of 16.
__attribute__((target("pclmul"))) uint32_t CrcFolded(const uint8_t* p,
                                                     size_t len, uint32_t c) {
  auto load = [](const uint8_t* q) {
    return _mm_loadu_si128(reinterpret_cast<const __m128i*>(q));
  };
  __m128i x0 = _mm_xor_si128(load(p), _mm_cvtsi32_si128(static_cast<int>(c)));
  __m128i x1 = load(p + 16), x2 = load(p + 32), x3 = load(p + 48);
  p += 64;
  len -= 64;
  const __m128i k64 = _mm_set_epi64x(kFold64Hi, kFold64Lo);
  for (; len >= 64; p += 64, len -= 64) {
    x0 = FoldInto(x0, k64, load(p));
    x1 = FoldInto(x1, k64, load(p + 16));
    x2 = FoldInto(x2, k64, load(p + 32));
    x3 = FoldInto(x3, k64, load(p + 48));
  }
  const __m128i k16 = _mm_set_epi64x(kFold16Hi, kFold16Lo);
  x0 = FoldInto(x0, k16, x1);
  x0 = FoldInto(x0, k16, x2);
  x0 = FoldInto(x0, k16, x3);
  for (; len >= 16; p += 16, len -= 16) x0 = FoldInto(x0, k16, load(p));
  // What is left is 16 bytes that stand for the whole message: the
  // register over them, from zero, is the register over the message.
  uint8_t rest[16];
  _mm_storeu_si128(reinterpret_cast<__m128i*>(rest), x0);
  return CrcSliced(rest, sizeof(rest), 0);
}
#endif

}  // namespace

Crc32Impl Crc32Chosen() {
#if defined(__x86_64__)
  static const Crc32Impl chosen = __builtin_cpu_supports("pclmul")
                                      ? Crc32Impl::kFolded
                                      : Crc32Impl::kSliced;
  return chosen;
#else
  return Crc32Impl::kSliced;
#endif
}

uint32_t Crc32With(Crc32Impl impl, const void* data, size_t len,
                   uint32_t seed) {
  const uint8_t* p = static_cast<const uint8_t*>(data);
  uint32_t c = seed ^ 0xFFFFFFFFu;
#if defined(__x86_64__)
  if (impl == Crc32Impl::kFolded && len >= 64) {
    size_t n = len & ~static_cast<size_t>(15);
    c = CrcFolded(p, n, c);
    p += n;
    len -= n;
  }
#else
  (void)impl;
#endif
  return CrcSliced(p, len, c) ^ 0xFFFFFFFFu;
}

uint32_t Crc32(const void* data, size_t len, uint32_t seed) {
  return Crc32With(Crc32Chosen(), data, len, seed);
}

// -- sha1 -----------------------------------------------------------------

static inline uint32_t Rotl(uint32_t x, int n) {
  return (x << n) | (x >> (32 - n));
}

static void Sha1Compress(uint32_t h[5], const uint8_t block[64]) {
  uint32_t w[80];
  for (int t = 0; t < 16; ++t) {
    w[t] = (static_cast<uint32_t>(block[t * 4]) << 24) |
           (static_cast<uint32_t>(block[t * 4 + 1]) << 16) |
           (static_cast<uint32_t>(block[t * 4 + 2]) << 8) | block[t * 4 + 3];
  }
  for (int t = 16; t < 80; ++t)
    w[t] = Rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1);
  uint32_t a = h[0], b = h[1], c = h[2], d = h[3], e = h[4];
  for (int t = 0; t < 80; ++t) {
    uint32_t f, k;
    if (t < 20) {
      f = (b & c) | (~b & d);
      k = 0x5A827999u;
    } else if (t < 40) {
      f = b ^ c ^ d;
      k = 0x6ED9EBA1u;
    } else if (t < 60) {
      f = (b & c) | (b & d) | (c & d);
      k = 0x8F1BBCDCu;
    } else {
      f = b ^ c ^ d;
      k = 0xCA62C1D6u;
    }
    uint32_t tmp = Rotl(a, 5) + f + e + k + w[t];
    e = d;
    d = c;
    c = Rotl(b, 30);
    b = a;
    a = tmp;
  }
  h[0] += a;
  h[1] += b;
  h[2] += c;
  h[3] += d;
  h[4] += e;
}

Sha1Stream::Sha1Stream() : total_(0), buf_len_(0) {
  h_[0] = 0x67452301u;
  h_[1] = 0xEFCDAB89u;
  h_[2] = 0x98BADCFEu;
  h_[3] = 0x10325476u;
  h_[4] = 0xC3D2E1F0u;
}

// SHA-NI hardware path (common/sha1_ni.cc, own TU: needs -msha);
// resolved once — __builtin_cpu_supports reads cpuid.
bool Sha1NiSupported();
void Sha1NiCompress(uint32_t h[5], const uint8_t* data, size_t nblocks);
static const bool kHaveSha1Ni = Sha1NiSupported();

void Sha1Stream::Update(const void* data, size_t len) {
  const uint8_t* p = static_cast<const uint8_t*>(data);
  total_ += len;
  if (buf_len_ > 0) {
    size_t need = 64 - buf_len_;
    size_t take = len < need ? len : need;
    std::memcpy(buf_ + buf_len_, p, take);
    buf_len_ += take;
    p += take;
    len -= take;
    if (buf_len_ == 64) {
      if (kHaveSha1Ni) Sha1NiCompress(h_, buf_, 1);
      else Sha1Compress(h_, buf_);
      buf_len_ = 0;
    }
  }
  if (len >= 64) {
    size_t nblocks = len / 64;
    if (kHaveSha1Ni) {
      Sha1NiCompress(h_, p, nblocks);
    } else {
      for (size_t i = 0; i < nblocks; ++i) Sha1Compress(h_, p + i * 64);
    }
    p += nblocks * 64;
    len -= nblocks * 64;
  }
  if (len > 0) {
    std::memcpy(buf_, p, len);
    buf_len_ = len;
  }
}

Sha1Digest Sha1Stream::Final() {
  uint64_t bit_len = total_ * 8;
  uint8_t pad = 0x80;
  Update(&pad, 1);
  uint8_t zero = 0;
  while (buf_len_ != 56) Update(&zero, 1);
  uint8_t len_bytes[8];
  for (int i = 7; i >= 0; --i) {
    len_bytes[i] = static_cast<uint8_t>(bit_len & 0xFF);
    bit_len >>= 8;
  }
  // Update() counts these toward total_, but bit_len is already latched.
  Update(len_bytes, 8);
  Sha1Digest d;
  for (int i = 0; i < 5; ++i) PutInt32BE(h_[i], d.bytes + i * 4);
  return d;
}

Sha1Digest Sha1(const void* data, size_t len) {
  Sha1Stream s;
  s.Update(data, len);
  return s.Final();
}

std::string BytesToHex(const uint8_t* data, size_t len) {
  static const char* kHex = "0123456789abcdef";
  std::string out(len * 2, '0');
  for (size_t i = 0; i < len; ++i) {
    out[i * 2] = kHex[data[i] >> 4];
    out[i * 2 + 1] = kHex[data[i] & 0xF];
  }
  return out;
}

std::string Sha1Digest::Hex() const { return BytesToHex(bytes, 20); }

void AppendJsonString(std::string* out, std::string_view s) {
  out->push_back('"');
  for (char ch : s) {
    switch (ch) {
      case '"': *out += "\\\""; break;
      case '\\': *out += "\\\\"; break;
      case '\n': *out += "\\n"; break;
      case '\r': *out += "\\r"; break;
      case '\t': *out += "\\t"; break;
      default:
        if (static_cast<unsigned char>(ch) < 0x20) {
          char buf[8];
          snprintf(buf, sizeof(buf), "\\u%04x", ch & 0xFF);
          *out += buf;
        } else {
          out->push_back(ch);
        }
    }
  }
  out->push_back('"');
}

bool HexToBytes(std::string_view hex, std::string* out) {
  if (hex.size() % 2 != 0) return false;
  auto nib = [](char c) -> int {
    if (c >= '0' && c <= '9') return c - '0';
    if (c >= 'a' && c <= 'f') return c - 'a' + 10;
    if (c >= 'A' && c <= 'F') return c - 'A' + 10;
    return -1;
  };
  std::string tmp;
  tmp.reserve(hex.size() / 2);
  for (size_t i = 0; i < hex.size(); i += 2) {
    int hi = nib(hex[i]), lo = nib(hex[i + 1]);
    if (hi < 0 || lo < 0) return false;
    tmp.push_back(static_cast<char>((hi << 4) | lo));
  }
  out->append(tmp);
  return true;
}

}  // namespace fdfs
