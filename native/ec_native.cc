// Host-CPU GF(2^8) region codec + crc32c — the native runtime kernels.
//
// Role: the reference accelerates its erasure-code hot loop with vendored
// SIMD libraries (isa-l's ec_encode_data, reference
// src/erasure-code/isa/ErasureCodeIsa.cc:129; jerasure/gf-complete SSE
// region ops) and its checksums with runtime-dispatched crc32c kernels
// (reference src/common/crc32c.cc:17).  This file provides the same two
// capabilities for the TPU framework's host side, written from the standard
// published techniques (split-nibble PSHUFB multiply tables; CRC32C via the
// SSE4.2 instruction, three interleaved chains merged by shift tables, with
// a table-driven fallback) — no reference code.
//
// It is used as (a) the honest host-CPU baseline the TPU path is measured
// against, and (b) the host verify/fallback path when no accelerator is up.
//
// Exposed C ABI (consumed via ctypes from ceph_tpu.native):
//   gf256_encode(M, m, k, tables, data, out, n)   out = M @ data over GF(2^8)
//   gf256_region_xor(src, dst, n)                 dst ^= src
//   crc32c(crc, data, n) -> uint32_t              Castagnoli CRC
//   crc32c_blocks(data, nblocks, bs, seed, out)   per-block CRCs (Checksummer)
//   ec_native_crc32c_impl() -> "hw3" | "sw"       the kernel crc32c() runs
//   ec_native_crc32c_sw(crc, data, n)             the table kernel, always
//   frame_pack(...)                               msgr2 frame codec: preamble,
//                                                 segments and crcs, one blob
//   frame_crcs(...)                               preamble and crcs alone: the
//                                                 segments go by reference
//   frame_verify_body(...)                        a received body's crcs
//   ec_native_have_avx2() / ec_native_have_sse42()

#include <cstdint>
#include <cstddef>
#include <cstring>

#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace {

// ---------------------------------------------------------------------------
// GF(2^8) region multiply-accumulate: dst ^= c * src
// Split-nibble tables: c*x == TLO[x & 15] ^ THI[x >> 4]  (linearity over GF2).
// `tab` points at 32 bytes: TLO[0..15] then THI[0..15] for this coefficient.
// ---------------------------------------------------------------------------

void mul_xor_scalar(const uint8_t* tab, const uint8_t* src, uint8_t* dst,
                    size_t n) {
  const uint8_t* tlo = tab;
  const uint8_t* thi = tab + 16;
  for (size_t i = 0; i < n; i++)
    dst[i] ^= (uint8_t)(tlo[src[i] & 15] ^ thi[src[i] >> 4]);
}

void xor_scalar(const uint8_t* src, uint8_t* dst, size_t n) {
  size_t i = 0;
  for (; i + 8 <= n; i += 8) {
    uint64_t a, b;
    memcpy(&a, dst + i, 8);
    memcpy(&b, src + i, 8);
    a ^= b;
    memcpy(dst + i, &a, 8);
  }
  for (; i < n; i++) dst[i] ^= src[i];
}

#if defined(__x86_64__)
__attribute__((target("avx2")))
void mul_xor_avx2(const uint8_t* tab, const uint8_t* src, uint8_t* dst,
                  size_t n) {
  const __m256i lo =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)tab));
  const __m256i hi =
      _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)(tab + 16)));
  const __m256i mask = _mm256_set1_epi8(0x0f);
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i s = _mm256_loadu_si256((const __m256i*)(src + i));
    __m256i d = _mm256_loadu_si256((const __m256i*)(dst + i));
    __m256i l = _mm256_shuffle_epi8(lo, _mm256_and_si256(s, mask));
    __m256i h = _mm256_shuffle_epi8(
        hi, _mm256_and_si256(_mm256_srli_epi16(s, 4), mask));
    _mm256_storeu_si256((__m256i*)(dst + i),
                        _mm256_xor_si256(d, _mm256_xor_si256(l, h)));
  }
  if (i < n) mul_xor_scalar(tab, src + i, dst + i, n - i);
}

__attribute__((target("avx2")))
void xor_avx2(const uint8_t* src, uint8_t* dst, size_t n) {
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i s = _mm256_loadu_si256((const __m256i*)(src + i));
    __m256i d = _mm256_loadu_si256((const __m256i*)(dst + i));
    _mm256_storeu_si256((__m256i*)(dst + i), _mm256_xor_si256(d, s));
  }
  if (i < n) xor_scalar(src + i, dst + i, n - i);
}

bool have_avx2() { return __builtin_cpu_supports("avx2"); }
bool have_sse42() { return __builtin_cpu_supports("sse4.2"); }
#else
bool have_avx2() { return false; }
bool have_sse42() { return false; }
#endif

void mul_xor(const uint8_t* tab, const uint8_t* src, uint8_t* dst, size_t n) {
#if defined(__x86_64__)
  if (have_avx2()) { mul_xor_avx2(tab, src, dst, n); return; }
#endif
  mul_xor_scalar(tab, src, dst, n);
}

void region_xor(const uint8_t* src, uint8_t* dst, size_t n) {
#if defined(__x86_64__)
  if (have_avx2()) { xor_avx2(src, dst, n); return; }
#endif
  xor_scalar(src, dst, n);
}

// ---------------------------------------------------------------------------
// crc32c (Castagnoli, poly 0x1EDC6F41 reflected = 0x82F63B78)
// ---------------------------------------------------------------------------

// Slice-by-8 tables, built once: a function-local static is initialised
// under the compiler's guard, so shard threads that race the first call
// all see complete tables.
struct Crc32cTable {
  uint32_t t[8][256];
  Crc32cTable() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int j = 0; j < 8; j++)
        c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : (c >> 1);
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = t[0][i];
      for (int s = 1; s < 8; s++) {
        c = t[0][c & 0xff] ^ (c >> 8);
        t[s][i] = c;
      }
    }
  }
};

const Crc32cTable& crc32c_table() {
  static const Crc32cTable table;
  return table;
}

uint32_t crc32c_sw(uint32_t crc, const uint8_t* data, size_t n) {
  const uint32_t (*t)[256] = crc32c_table().t;
  // slice-by-8
  while (n >= 8) {
    uint64_t v;
    memcpy(&v, data, 8);
    v ^= crc;
    crc = t[7][v & 0xff] ^ t[6][(v >> 8) & 0xff] ^
          t[5][(v >> 16) & 0xff] ^ t[4][(v >> 24) & 0xff] ^
          t[3][(v >> 32) & 0xff] ^ t[2][(v >> 40) & 0xff] ^
          t[1][(v >> 48) & 0xff] ^ t[0][(v >> 56) & 0xff];
    data += 8;
    n -= 8;
  }
  while (n--) crc = t[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
  return crc;
}

#if defined(__x86_64__)
// The crc32 instruction has a latency of 3 cycles and a throughput of one
// a cycle, so one dependent chain (8 bytes every 3 cycles) leaves two
// thirds of the unit idle. Three chains over three adjacent blocks fill
// it; they are merged with the operator "append `len` zero bytes", which
// is linear over GF(2) on the raw register (no pre- or post-inversion
// here, ceph's convention):
//   crc(s, A|B|C) = zeros_2L(crc(s, A)) ^ zeros_L(crc(0, B)) ^ crc(0, C).
// The operator for a fixed block length is a 4 x 256 table (the scheme of
// Adler's crc32c.c; upstream's crc32c_intel_fast merges with pclmulqdq).
// Long blocks amortise the two merges over 24 KiB; short blocks serve the
// 4 KiB checksum blocks and the tail of a long run.
constexpr size_t CRC_LONG = 8192;
constexpr size_t CRC_SHORT = 256;

struct Crc32cZeros {
  uint32_t lng[4][256];
  uint32_t shrt[4][256];

  // t[j][b] = the register after `len` zero bytes from b << 8j
  static void build(uint32_t (*t)[256], size_t len) {
    static const uint8_t zeros[CRC_LONG] = {0};
    uint32_t bit[32];
    for (int i = 0; i < 32; i++)
      bit[i] = crc32c_sw(1u << i, zeros, len);
    for (int j = 0; j < 4; j++)
      for (uint32_t b = 0; b < 256; b++) {
        uint32_t c = 0;
        for (int i = 0; i < 8; i++)
          if (b & (1u << i)) c ^= bit[8 * j + i];
        t[j][b] = c;
      }
  }
  Crc32cZeros() {
    build(lng, CRC_LONG);
    build(shrt, CRC_SHORT);
  }
};

const Crc32cZeros& crc32c_zeros() {
  static const Crc32cZeros z;     // once, under the compiler's guard
  return z;
}

inline uint32_t crc32c_shift(const uint32_t (*t)[256], uint32_t c) {
  return t[0][c & 0xff] ^ t[1][(c >> 8) & 0xff] ^ t[2][(c >> 16) & 0xff] ^
         t[3][c >> 24];
}

// Three chains over data[0:3*len], len a multiple of 8.
__attribute__((target("sse4.2")))
inline uint64_t crc32c_hw_x3(uint64_t c0, const uint8_t* data, size_t len,
                             const uint32_t (*t)[256]) {
  uint64_t c1 = 0, c2 = 0;
  const uint8_t* end = data + len;
  do {
    uint64_t a, b, c;
    memcpy(&a, data, 8);
    memcpy(&b, data + len, 8);
    memcpy(&c, data + 2 * len, 8);
    c0 = _mm_crc32_u64(c0, a);
    c1 = _mm_crc32_u64(c1, b);
    c2 = _mm_crc32_u64(c2, c);
    data += 8;
  } while (data < end);
  c0 = crc32c_shift(t, (uint32_t)c0) ^ c1;
  return crc32c_shift(t, (uint32_t)c0) ^ c2;
}

__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t crc, const uint8_t* data, size_t n) {
  uint64_t c = crc;
  // up to an 8-byte boundary, so the chains load aligned words
  while (n && ((uintptr_t)data & 7)) {
    c = _mm_crc32_u8((uint32_t)c, *data++);
    n--;
  }
  if (n >= 3 * CRC_SHORT) {
    const Crc32cZeros& z = crc32c_zeros();
    while (n >= 3 * CRC_LONG) {
      c = crc32c_hw_x3(c, data, CRC_LONG, z.lng);
      data += 3 * CRC_LONG;
      n -= 3 * CRC_LONG;
    }
    while (n >= 3 * CRC_SHORT) {
      c = crc32c_hw_x3(c, data, CRC_SHORT, z.shrt);
      data += 3 * CRC_SHORT;
      n -= 3 * CRC_SHORT;
    }
  }
  // the tail, under 768 bytes: one chain
  while (n >= 8) {
    uint64_t v;
    memcpy(&v, data, 8);
    c = _mm_crc32_u64(c, v);
    data += 8;
    n -= 8;
  }
  uint32_t c32 = (uint32_t)c;
  while (n--) c32 = _mm_crc32_u8(c32, *data++);
  return c32;
}
#endif

}  // namespace

extern "C" {

int ec_native_have_avx2() { return have_avx2() ? 1 : 0; }
int ec_native_have_sse42() { return have_sse42() ? 1 : 0; }

// out(m,n) = M(m,k) @ data(k,n) over GF(2^8); `tables` is the 256x32 split
// table block: tables[c*32 + v] = mul(c, v) for v<16, mul(c, (v-16)<<4) else.
void gf256_encode(const uint8_t* M, int m, int k, const uint8_t* tables,
                  const uint8_t* data, uint8_t* out, size_t n) {
  for (int i = 0; i < m; i++) {
    uint8_t* dst = out + (size_t)i * n;
    memset(dst, 0, n);
    for (int j = 0; j < k; j++) {
      uint8_t c = M[(size_t)i * k + j];
      if (c == 0) continue;
      const uint8_t* src = data + (size_t)j * n;
      if (c == 1)
        region_xor(src, dst, n);
      else
        mul_xor(tables + (size_t)c * 32, src, dst, n);
    }
  }
}

void gf256_region_xor(const uint8_t* src, uint8_t* dst, size_t n) {
  region_xor(src, dst, n);
}

uint32_t crc32c(uint32_t crc, const uint8_t* data, size_t n) {
#if defined(__x86_64__)
  if (have_sse42()) return crc32c_hw(crc, data, n);
#endif
  return crc32c_sw(crc, data, n);
}

// The kernel crc32c() dispatches to on this host: "hw3" (the crc32
// instruction, three interleaved chains) or "sw" (slice-by-8 tables).
const char* ec_native_crc32c_impl() {
#if defined(__x86_64__)
  if (have_sse42()) return "hw3";
#endif
  return "sw";
}

// The table kernel whatever the host has: what the tests hold the
// dispatched one to.
uint32_t ec_native_crc32c_sw(uint32_t crc, const uint8_t* data, size_t n) {
  return crc32c_sw(crc, data, n);
}

// Per-block CRCs over a contiguous buffer of nblocks x block_size bytes —
// the Checksummer batch shape (reference src/common/Checksummer.h:195-234).
void crc32c_blocks(const uint8_t* data, size_t nblocks, size_t block_size,
                   uint32_t seed, uint32_t* out) {
  for (size_t b = 0; b < nblocks; b++)
    out[b] = crc32c(seed, data + b * block_size, block_size);
}

// ---------------------------------------------------------------------------
// msgr2 frame codec (the hot path of ceph_tpu/msg/frames.py): one C call
// does a frame's codec work — little-endian preamble (magic u16, tag u8,
// seg_count u8, seg_len u32*, preamble crc u32) and each segment's trailing
// crc32c — instead of 2+nseg ctypes round trips and a Python scatter loop
// per frame. frame_pack also copies the segments between them into one wire
// blob (small frames); frame_crcs copies nothing (frames whose segments are
// sent from where they lie). Segments arrive as a FLATTENED part list
// (seg_parts[i] parts belong to segment i) so scatter-gather payloads (the
// sub-op batch envelope's concatenated message datas) need no intermediate
// join: the segment crc chains across its parts. Layout is bit-identical to
// the pure-Python path in frames.py, which stays the fallback when this
// library is unavailable.
// ---------------------------------------------------------------------------

static inline void put_u16le(uint8_t* p, uint16_t v) {
  p[0] = (uint8_t)v;
  p[1] = (uint8_t)(v >> 8);
}

static inline void put_u32le(uint8_t* p, uint32_t v) {
  p[0] = (uint8_t)v;
  p[1] = (uint8_t)(v >> 8);
  p[2] = (uint8_t)(v >> 16);
  p[3] = (uint8_t)(v >> 24);
}

// The preamble of a frame into `out` (8 + 4*nseg bytes): magic, tag,
// segment count, each segment's length summed over its parts, and the crc
// of all that. Returns the end.
static uint8_t* put_preamble(uint32_t magic, uint32_t tag, int nseg,
                             const uint64_t* seg_parts,
                             const uint64_t* part_lens, uint8_t* out) {
  uint8_t* p = out;
  put_u16le(p, (uint16_t)magic);
  p[2] = (uint8_t)tag;
  p[3] = (uint8_t)nseg;
  p += 4;
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint64_t len = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++)
      len += part_lens[part + j];
    part += seg_parts[s];
    put_u32le(p, (uint32_t)len);
    p += 4;
  }
  put_u32le(p, crc32c(0, out, (size_t)(p - out)));
  return p + 4;
}

// Pack one frame into `out` (caller sizes it: 4 + 4*nseg + 4 +
// sum(seg_len + 4)). Returns total bytes written.
uint64_t frame_pack(uint32_t magic, uint32_t tag, int nseg,
                    const uint64_t* seg_parts,       // parts per segment
                    const uint8_t* const* parts,     // flattened part ptrs
                    const uint64_t* part_lens,       // flattened part lens
                    uint8_t* out) {
  uint8_t* p = put_preamble(magic, tag, nseg, seg_parts, part_lens, out);
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint32_t crc = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++) {
      size_t n = (size_t)part_lens[part + j];
      if (n) {
        memcpy(p, parts[part + j], n);
        crc = crc32c(crc, p, n);
        p += n;
      }
    }
    part += seg_parts[s];
    put_u32le(p, crc);
    p += 4;
  }
  return (uint64_t)(p - out);
}

// The same frame with nothing copied: the preamble and then each segment's
// trailing crc (4 bytes a segment, chained over its parts) into `out`
// (8 + 8*nseg bytes). The caller sends [preamble, parts of segment 0,
// crc 0, ...] from where the parts lie. Returns the bytes written.
uint64_t frame_crcs(uint32_t magic, uint32_t tag, int nseg,
                    const uint64_t* seg_parts,
                    const uint8_t* const* parts,
                    const uint64_t* part_lens,
                    uint8_t* out) {
  uint8_t* p = put_preamble(magic, tag, nseg, seg_parts, part_lens, out);
  size_t part = 0;
  for (int s = 0; s < nseg; s++) {
    uint32_t crc = 0;
    for (uint64_t j = 0; j < seg_parts[s]; j++) {
      size_t n = (size_t)part_lens[part + j];
      if (n) crc = crc32c(crc, parts[part + j], n);
    }
    part += seg_parts[s];
    put_u32le(p, crc);
    p += 4;
  }
  return (uint64_t)(p - out);
}

// Verify a frame body (nseg runs of [seg bytes | crc32c u32]) in one call.
// Returns -1 when every segment checks out, else the index of the first
// segment whose trailing crc mismatches. The caller has already validated
// the preamble (its crc covers the lengths used here).
int frame_verify_body(const uint8_t* body, const uint64_t* seg_lens,
                      int nseg) {
  const uint8_t* p = body;
  for (int s = 0; s < nseg; s++) {
    size_t n = (size_t)seg_lens[s];
    uint32_t want = (uint32_t)p[n] | ((uint32_t)p[n + 1] << 8) |
                    ((uint32_t)p[n + 2] << 16) | ((uint32_t)p[n + 3] << 24);
    if (crc32c(0, p, n) != want) return s;
    p += n + 4;
  }
  return -1;
}

// De-interleave: planes [first, first + count) of `src`, S stripes of n
// chunks of C bytes each, into `dst`, count planes of S * C bytes one
// after another: a shard's chunks of every stripe made contiguous, for
// any run of shards in one call (and so in one stretch without the GIL).
void planes_from_stripes(const uint8_t* src, size_t S, size_t n, size_t C,
                         size_t first, size_t count, uint8_t* dst) {
  for (size_t s = 0; s < S; s++) {
    const uint8_t* row = src + (s * n + first) * C;
    for (size_t p = 0; p < count; p++)
      memcpy(dst + (p * S + s) * C, row + p * C, C);
  }
}

}  // extern "C"
