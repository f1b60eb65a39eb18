// crc32c host kernel (Castagnoli, reflected poly 0x82F63B78).
//
// Behavioral twin of the reference's ceph_crc32c family
// (reference src/common/sctp_crc32.c:update_crc32 — plain reflected
// table update, caller passes the seed, no init/final inversion;
// reference src/common/crc32c.cc:216 ceph_crc32c_zeros for the
// null-buffer "crc of zeros" path).  Two code paths, one value: the
// CPU's CRC32C instruction where it has one (x86-64 SSE4.2, found at
// run time with __builtin_cpu_supports, so the build needs no -m flag;
// aarch64 where the compiler defines __ARM_FEATURE_CRC32), three
// streams interleaved to hide the instruction's latency, and the
// slice-by-8 tables everywhere else.  The tables stay the oracle:
// `table_only` forces them, for the tests.
#include <cstddef>
#include <cstdint>
#include <cstring>

#if defined(__x86_64__)
#include <nmmintrin.h>
#define CRC_HW_NAME "sse4.2"
#define CRC_HW_TARGET __attribute__((target("sse4.2")))
#define CRC_HW_U8(c, v) _mm_crc32_u8(static_cast<uint32_t>(c), v)
#define CRC_HW_U64(c, v) _mm_crc32_u64(c, v)
#elif defined(__aarch64__) && defined(__ARM_FEATURE_CRC32)
#include <arm_acle.h>
#define CRC_HW_NAME "armv8"
#define CRC_HW_TARGET
#define CRC_HW_U8(c, v) __crc32cb(static_cast<uint32_t>(c), v)
#define CRC_HW_U64(c, v) __crc32cd(static_cast<uint32_t>(c), v)
#endif

namespace {

struct Tables {
  uint32_t t[8][256];
  Tables() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c & 1) ? 0x82F63B78u ^ (c >> 1) : c >> 1;
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
const Tables kT;

// crc of `len` zero bytes: the byte step degenerates to
// crc = T[crc & 0xff] ^ (crc >> 8); once crc hits 0 it stays 0.
uint32_t crc_zeros(uint32_t crc, size_t len) {
  while (len >= 1 && crc != 0) {
    crc = kT.t[0][crc & 0xff] ^ (crc >> 8);
    len--;
  }
  return crc;
}

uint32_t crc_table(uint32_t crc, const uint8_t* data, size_t len) {
  while (len && (reinterpret_cast<uintptr_t>(data) & 7)) {
    crc = kT.t[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
    len--;
  }
  while (len >= 8) {
    uint64_t v;
    std::memcpy(&v, data, 8);
    v ^= crc;
    crc = kT.t[7][v & 0xff] ^ kT.t[6][(v >> 8) & 0xff] ^
          kT.t[5][(v >> 16) & 0xff] ^ kT.t[4][(v >> 24) & 0xff] ^
          kT.t[3][(v >> 32) & 0xff] ^ kT.t[2][(v >> 40) & 0xff] ^
          kT.t[1][(v >> 48) & 0xff] ^ kT.t[0][(v >> 56) & 0xff];
    data += 8;
    len -= 8;
  }
  while (len--) crc = kT.t[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
  return crc;
}

#ifdef CRC_HW_NAME
// Three streams of kLane bytes run side by side (the instruction has a
// latency of three cycles and a throughput of one); the update is
// linear, so crc(A||B) = advance(crc(A), |B| zero bytes) ^ crc_0(B),
// and kAdvance is that advance over kLane zero bytes, by byte of crc.
constexpr size_t kLane = 2048;

struct Advance {
  uint32_t t[4][256];
  Advance() {
    for (int k = 0; k < 4; k++)
      for (uint32_t b = 0; b < 256; b++) t[k][b] = crc_zeros(b << (8 * k), kLane);
  }
  uint32_t operator()(uint64_t c) const {
    return t[0][c & 0xff] ^ t[1][(c >> 8) & 0xff] ^ t[2][(c >> 16) & 0xff] ^
           t[3][(c >> 24) & 0xff];
  }
};
const Advance kAdvance;

inline uint64_t load64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;
}

CRC_HW_TARGET uint32_t crc_hw(uint32_t crc, const uint8_t* data, size_t len) {
  uint64_t c0 = crc;
  while (len && (reinterpret_cast<uintptr_t>(data) & 7)) {
    c0 = CRC_HW_U8(c0, *data++);
    len--;
  }
  while (len >= 3 * kLane) {
    uint64_t c1 = 0, c2 = 0;
    for (size_t i = 0; i < kLane; i += 8) {
      c0 = CRC_HW_U64(c0, load64(data + i));
      c1 = CRC_HW_U64(c1, load64(data + kLane + i));
      c2 = CRC_HW_U64(c2, load64(data + 2 * kLane + i));
    }
    c0 = kAdvance(c0) ^ c1;
    c0 = kAdvance(c0) ^ c2;
    data += 3 * kLane;
    len -= 3 * kLane;
  }
  while (len >= 8) {
    c0 = CRC_HW_U64(c0, load64(data));
    data += 8;
    len -= 8;
  }
  while (len--) c0 = CRC_HW_U8(c0, *data++);
  return static_cast<uint32_t>(c0);
}

// Whole chunks three at a time, one lane each; returns the bytes done.
CRC_HW_TARGET size_t crc_hw_chunks3(uint32_t seed, const uint8_t* data,
                                    size_t len, size_t chunk, uint32_t* out) {
  size_t at = 0;
  for (; at + 3 * chunk <= len; at += 3 * chunk) {
    const uint8_t* p = data + at;
    uint64_t c0 = seed, c1 = seed, c2 = seed;
    for (size_t i = 0; i < chunk; i += 8) {
      c0 = CRC_HW_U64(c0, load64(p + i));
      c1 = CRC_HW_U64(c1, load64(p + chunk + i));
      c2 = CRC_HW_U64(c2, load64(p + 2 * chunk + i));
    }
    *out++ = static_cast<uint32_t>(c0);
    *out++ = static_cast<uint32_t>(c1);
    *out++ = static_cast<uint32_t>(c2);
  }
  return at;
}

#if defined(__x86_64__)
const bool kHaveHw = __builtin_cpu_supports("sse4.2");
#else
const bool kHaveHw = true;
#endif
#else
#define CRC_HW_NAME "table"
const bool kHaveHw = false;
constexpr auto crc_hw = crc_table;
#endif

}  // namespace

extern "C" {

// Matches ceph_crc32c(seed, data, len); data may be null (= zeros).
uint32_t ceph_tpu_crc32c(uint32_t crc, const uint8_t* data, size_t len,
                         int table_only) {
  if (data == nullptr) return crc_zeros(crc, len);
  return kHaveHw && !table_only ? crc_hw(crc, data, len)
                                : crc_table(crc, data, len);
}

// The crc of every `chunk` bytes of data (the last chunk may be short),
// each from `seed`, into out[0 .. ceil(len / chunk)): a store's checksum
// per csum chunk, all of a buffer's in one pass and one call.  Three
// whole chunks run side by side as crc_hw's three lanes do, and need no
// advance: they are three separate sums.
void ceph_tpu_crc32c_chunks(uint32_t seed, const uint8_t* data, size_t len,
                            size_t chunk, uint32_t* out, int table_only) {
  const bool hw = kHaveHw && !table_only;
  size_t at = 0;
#ifdef CRC_HW_U64
  if (hw && chunk % 8 == 0) at = crc_hw_chunks3(seed, data, len, chunk, out);
  out += at / (chunk ? chunk : 1);
#endif
  for (; at < len; at += chunk) {
    const size_t n = len - at < chunk ? len - at : chunk;
    *out++ = hw ? crc_hw(seed, data + at, n) : crc_table(seed, data + at, n);
  }
}

// Which path ceph_tpu_crc32c takes on this CPU.
const char* ceph_tpu_crc_backend() { return kHaveHw ? CRC_HW_NAME : "table"; }

// XOR-accumulate src into dst (region parity; reference
// src/erasure-code/isa/xor_op.cc semantics, compiler-vectorized).
void ceph_tpu_xor_region(uint8_t* dst, const uint8_t* src, size_t len) {
  size_t i = 0;
  for (; i + 8 <= len; i += 8) {
    uint64_t a, b;
    std::memcpy(&a, dst + i, 8);
    std::memcpy(&b, src + i, 8);
    a ^= b;
    std::memcpy(dst + i, &a, 8);
  }
  for (; i < len; i++) dst[i] ^= src[i];
}

}  // extern "C"
