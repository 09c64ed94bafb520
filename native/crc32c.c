/* crc32c (Castagnoli), raw seed in/out, on the CPU's CRC instruction.
 *
 * Native runtime piece of the TPU framework: the per-shard cumulative
 * chunk hash (HashInfo) and transport frame checksums need CPU-side
 * crc32c at memory bandwidth, which a Python byte loop cannot provide.
 * Semantics match the reference's ceph_crc32c(seed, buf, len): the
 * caller passes the running crc (no implicit pre/post inversion), so
 * cumulative hashing chains calls directly
 * (behavioral ref: src/common/sctp_crc32.c, src/common/crc32c.h).
 *
 * Implementation, chosen once at load like the reference's
 * ceph_choose_crc32 (src/common/crc32c.cc, src/arch/ probes):
 *   sse42  x86-64 SSE4.2 crc32, 8 bytes a step, three interleaved
 *          streams joined by shifting the earlier ones over the later
 *          blocks' length (the crc32c_intel_fast scheme)
 *   armv8  the ARMv8 CRC32C instructions, same three streams
 *   table  portable slice-by-8, the fallback
 * The instruction paths are compiled through function `target`
 * attributes, so the build line needs no -m flags.
 *
 * Build: cc -O3 -shared -fPIC crc32c.c -o libceph_tpu_native.so
 */
#include <stddef.h>
#include <stdint.h>
#include <string.h>

#define POLY 0x82F63B78u

static uint32_t table[8][256];

static uint32_t crc32c_table(uint32_t crc, const uint8_t *data, size_t len)
{
    /* head: align to 8 bytes */
    while (len && ((uintptr_t)data & 7)) {
        crc = table[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
        len--;
    }
    /* body: 8 bytes per step */
    while (len >= 8) {
        uint64_t word;
        memcpy(&word, data, 8);
        word ^= crc;
        crc = table[7][word & 0xff] ^
              table[6][(word >> 8) & 0xff] ^
              table[5][(word >> 16) & 0xff] ^
              table[4][(word >> 24) & 0xff] ^
              table[3][(word >> 32) & 0xff] ^
              table[2][(word >> 40) & 0xff] ^
              table[1][(word >> 48) & 0xff] ^
              table[0][(word >> 56) & 0xff];
        data += 8;
        len -= 8;
    }
    while (len--)
        crc = table[0][(crc ^ *data++) & 0xff] ^ (crc >> 8);
    return crc;
}

#if defined(__x86_64__) && (defined(__GNUC__) || defined(__clang__))
#include <nmmintrin.h>
#define HW_NAME "sse42"
#define HW_TARGET __attribute__((target("sse4.2")))
#define CRC_U64(c, w) ((uint32_t)_mm_crc32_u64((c), (w)))
#define CRC_U8(c, b) _mm_crc32_u8((c), (b))
static int hw_supported(void)
{
    __builtin_cpu_init();
    return __builtin_cpu_supports("sse4.2");
}
#elif defined(__aarch64__) && defined(__linux__) && \
    (defined(__GNUC__) || defined(__clang__))
#include <arm_acle.h>
#include <sys/auxv.h>
#ifndef HWCAP_CRC32
#define HWCAP_CRC32 (1 << 7)
#endif
#define HW_NAME "armv8"
#define HW_TARGET __attribute__((target("+crc")))
#define CRC_U64(c, w) __crc32cd((c), (w))
#define CRC_U8(c, b) __crc32cb((c), (b))
static int hw_supported(void)
{
    return (getauxval(AT_HWCAP) & HWCAP_CRC32) != 0;
}
#endif

#ifdef HW_NAME
/* Block lengths of the three-stream loops: LONG for the body of long
 * buffers, SHORT for what is left.  Both are powers of two (the zeros
 * operator below needs that). */
#define LONG 8192
#define SHORT 256

/* zeros_long[i][b]: the crc register after LONG zero bytes, from a
 * register holding byte b at position i; likewise zeros_short. */
static uint32_t zeros_long[4][256];
static uint32_t zeros_short[4][256];

static uint32_t gf2_matrix_times(const uint32_t *mat, uint32_t vec)
{
    uint32_t sum = 0;
    while (vec) {
        if (vec & 1)
            sum ^= *mat;
        vec >>= 1;
        mat++;
    }
    return sum;
}

static void gf2_matrix_square(uint32_t *square, const uint32_t *mat)
{
    int n;
    for (n = 0; n < 32; n++)
        square[n] = gf2_matrix_times(mat, mat[n]);
}

/* The operator that runs len zero bytes through the register; len a
 * power of two. */
static void zeros_op(uint32_t *even, size_t len)
{
    uint32_t odd[32];
    uint32_t row = 1;
    int n;

    odd[0] = POLY;                     /* one zero bit */
    for (n = 1; n < 32; n++) {
        odd[n] = row;
        row <<= 1;
    }
    gf2_matrix_square(even, odd);      /* two zero bits */
    gf2_matrix_square(odd, even);      /* four zero bits */
    /* each square doubles: the first puts one zero byte in even */
    do {
        gf2_matrix_square(even, odd);
        len >>= 1;
        if (len == 0)
            return;
        gf2_matrix_square(odd, even);
        len >>= 1;
    } while (len);
    memcpy(even, odd, sizeof(odd));
}

static void zeros_table(uint32_t zeros[4][256], size_t len)
{
    uint32_t op[32];
    uint32_t n;

    zeros_op(op, len);
    for (n = 0; n < 256; n++) {
        zeros[0][n] = gf2_matrix_times(op, n);
        zeros[1][n] = gf2_matrix_times(op, n << 8);
        zeros[2][n] = gf2_matrix_times(op, n << 16);
        zeros[3][n] = gf2_matrix_times(op, n << 24);
    }
}

static inline uint32_t shift(uint32_t zeros[4][256], uint32_t crc)
{
    return zeros[0][crc & 0xff] ^ zeros[1][(crc >> 8) & 0xff] ^
           zeros[2][(crc >> 16) & 0xff] ^ zeros[3][crc >> 24];
}

static inline uint64_t load64(const uint8_t *p)
{
    uint64_t w;
    memcpy(&w, p, 8);
    return w;
}

/* The register is linear in (register, data), so
 * crc(c, A|B|C) = shift(shift(crc(c, A)) ^ crc(0, B)) ^ crc(0, C)
 * for blocks A, B, C of one length, hashed side by side. */
#define THREE_STREAMS(BLOCK, ZEROS)                                    \
    while (len >= 3 * (BLOCK)) {                                       \
        uint32_t crc1 = 0, crc2 = 0;                                   \
        const uint8_t *end = data + (BLOCK);                           \
        do {                                                           \
            crc = CRC_U64(crc, load64(data));                          \
            crc1 = CRC_U64(crc1, load64(data + (BLOCK)));              \
            crc2 = CRC_U64(crc2, load64(data + 2 * (BLOCK)));          \
            data += 8;                                                 \
        } while (data < end);                                          \
        crc = shift(ZEROS, crc) ^ crc1;                                \
        crc = shift(ZEROS, crc) ^ crc2;                                \
        data += 2 * (BLOCK);                                           \
        len -= 3 * (BLOCK);                                            \
    }

HW_TARGET static uint32_t crc32c_hw(uint32_t crc, const uint8_t *data,
                                    size_t len)
{
    while (len && ((uintptr_t)data & 7)) {
        crc = CRC_U8(crc, *data++);
        len--;
    }
    THREE_STREAMS(LONG, zeros_long)
    THREE_STREAMS(SHORT, zeros_short)
    while (len >= 8) {
        crc = CRC_U64(crc, load64(data));
        data += 8;
        len -= 8;
    }
    while (len--)
        crc = CRC_U8(crc, *data++);
    return crc;
}
#endif

static uint32_t (*impl)(uint32_t, const uint8_t *, size_t) = crc32c_table;
static const char *impl_name = "table";

/* Tables built and the implementation chosen once at dlopen time
 * (constructor): no lazy-init publication race between threads. */
__attribute__((constructor)) static void init(void)
{
    uint32_t i, j, crc;
    for (i = 0; i < 256; i++) {
        crc = i;
        for (j = 0; j < 8; j++)
            crc = (crc & 1) ? (crc >> 1) ^ POLY : crc >> 1;
        table[0][i] = crc;
    }
    for (i = 0; i < 256; i++) {
        crc = table[0][i];
        for (j = 1; j < 8; j++) {
            crc = table[0][crc & 0xff] ^ (crc >> 8);
            table[j][i] = crc;
        }
    }
#ifdef HW_NAME
    if (hw_supported()) {
        zeros_table(zeros_long, LONG);
        zeros_table(zeros_short, SHORT);
        impl = crc32c_hw;
        impl_name = HW_NAME;
    }
#endif
}

uint32_t ceph_tpu_crc32c(uint32_t seed, const uint8_t *data, size_t len)
{
    return impl(seed, data, len);
}

/* The slice-by-8 fallback whatever the CPU offers (tests compare the
 * two). */
uint32_t ceph_tpu_crc32c_table(uint32_t seed, const uint8_t *data,
                               size_t len)
{
    return crc32c_table(seed, data, len);
}

const char *ceph_tpu_crc32c_impl(void)
{
    return impl_name;
}
