/* ristretto255 group operations for batched Schnorr verification.
 *
 * The host-side native layer of the session stack (the analog of the
 * reference's Rust mc-crypto-keys dependency, reference
 * types/src/lib.rs:13, README.md:199): field arithmetic mod 2^255-19
 * with 5x51-bit limbs (unsigned __int128 products), extended-Edwards
 * point ops, RFC 9496 ristretto decode/encode, a precomputed fixed-base
 * nibble table, and a Straus / Pippenger multi-scalar multiplication.
 * The single-signature check takes fully reduced 256-bit little-endian
 * scalars from Python; the chunk check does its own arithmetic mod L
 * and its own merlin challenges, so a chunk is one crossing.
 *
 * Exposed via ctypes (grapevine_tpu/native/__init__.py):
 *   r255_init()                     build the basepoint table (idempotent)
 *   r255_verify1(pub, R, s, k)      s*B == R + k*A          -> 1/0/-1
 *   r255_round_check(n, k, pubs, sigs, rand, prefix, msgs, mlens, ks,
 *                    elapsed_s)
 *       the n items as k contiguous chunks; per chunk: parse,
 *       challenges k_i, z_i from rand, then
 *       fixed(sum z_i*s_i) == sum z_i*R_i + (z_i*k_i)*A_i   -> 1/0/-1
 *       REENTRANT: every byte a chunk check writes is on its stack or
 *       in its own heap arena, so the k chunks run on k threads for
 *       the length of the call, and any number of calls side by side
 *   r255_chacha20_xor(key, nonce, counter, src, dst, n, threads)
 *       dst = src XOR the RFC 7539 ChaCha20 keystream from block
 *       `counter` on: the seal of the journal and of checkpoints
 *       (engine/checkpoint.py), eight blocks a pass, on up to `threads`
 *       threads for the length of the call                  -> 0/-1
 *
 * The group code is verification-only: it handles no secrets, so
 * variable-time arithmetic is fine (same stance as the pure-Python path
 * it accelerates, session/ristretto.py). ChaCha20 does take a secret
 * key, and is add / rotate / xor on registers throughout: no branch and
 * no address depends on key or data.
 *
 * Built by `cc -O2 -shared -fPIC -pthread` at first import; correctness is
 * pinned by cross-checking against the pure-Python implementation over
 * random points/scalars and the RFC 9496 test vectors
 * (tests/test_native_r255.py).
 */

#include <pthread.h>
#include <signal.h>
#include <stdint.h>
#include <stddef.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>

typedef uint64_t u64;
typedef unsigned __int128 u128;

#define MASK51 0x7FFFFFFFFFFFFULL

typedef struct { u64 v[5]; } fe;

/* ---------------- field arithmetic mod 2^255-19 ---------------- */

static void fe_zero(fe *r) { memset(r, 0, sizeof *r); }
static void fe_one(fe *r) { fe_zero(r); r->v[0] = 1; }
static void fe_copy(fe *r, const fe *a) { *r = *a; }

static void fe_add(fe *r, const fe *a, const fe *b) {
    for (int i = 0; i < 5; i++) r->v[i] = a->v[i] + b->v[i];
}

/* r = a - b, with a bias of 2p to keep limbs nonnegative */
static void fe_sub(fe *r, const fe *a, const fe *b) {
    r->v[0] = a->v[0] + 0xFFFFFFFFFFFDAULL - b->v[0];
    r->v[1] = a->v[1] + 0xFFFFFFFFFFFFEULL - b->v[1];
    r->v[2] = a->v[2] + 0xFFFFFFFFFFFFEULL - b->v[2];
    r->v[3] = a->v[3] + 0xFFFFFFFFFFFFEULL - b->v[3];
    r->v[4] = a->v[4] + 0xFFFFFFFFFFFFEULL - b->v[4];
}

static void fe_carry(fe *r) {
    for (int rep = 0; rep < 2; rep++) {
        u64 c;
        c = r->v[0] >> 51; r->v[0] &= MASK51; r->v[1] += c;
        c = r->v[1] >> 51; r->v[1] &= MASK51; r->v[2] += c;
        c = r->v[2] >> 51; r->v[2] &= MASK51; r->v[3] += c;
        c = r->v[3] >> 51; r->v[3] &= MASK51; r->v[4] += c;
        c = r->v[4] >> 51; r->v[4] &= MASK51; r->v[0] += c * 19;
    }
}

static void fe_mul(fe *r, const fe *a, const fe *b) {
    u128 t0, t1, t2, t3, t4;
    u64 a0 = a->v[0], a1 = a->v[1], a2 = a->v[2], a3 = a->v[3], a4 = a->v[4];
    u64 b0 = b->v[0], b1 = b->v[1], b2 = b->v[2], b3 = b->v[3], b4 = b->v[4];
    u64 b1_19 = b1 * 19, b2_19 = b2 * 19, b3_19 = b3 * 19, b4_19 = b4 * 19;

    t0 = (u128)a0*b0 + (u128)a1*b4_19 + (u128)a2*b3_19 + (u128)a3*b2_19 + (u128)a4*b1_19;
    t1 = (u128)a0*b1 + (u128)a1*b0    + (u128)a2*b4_19 + (u128)a3*b3_19 + (u128)a4*b2_19;
    t2 = (u128)a0*b2 + (u128)a1*b1    + (u128)a2*b0    + (u128)a3*b4_19 + (u128)a4*b3_19;
    t3 = (u128)a0*b3 + (u128)a1*b2    + (u128)a2*b1    + (u128)a3*b0    + (u128)a4*b4_19;
    t4 = (u128)a0*b4 + (u128)a1*b3    + (u128)a2*b2    + (u128)a3*b1    + (u128)a4*b0;

    u64 c;
    u64 r0 = (u64)t0 & MASK51; c = (u64)(t0 >> 51);
    t1 += c;
    u64 r1 = (u64)t1 & MASK51; c = (u64)(t1 >> 51);
    t2 += c;
    u64 r2 = (u64)t2 & MASK51; c = (u64)(t2 >> 51);
    t3 += c;
    u64 r3 = (u64)t3 & MASK51; c = (u64)(t3 >> 51);
    t4 += c;
    u64 r4 = (u64)t4 & MASK51; c = (u64)(t4 >> 51);
    r0 += c * 19;
    c = r0 >> 51; r0 &= MASK51; r1 += c;
    r->v[0] = r0; r->v[1] = r1; r->v[2] = r2; r->v[3] = r3; r->v[4] = r4;
}

static void fe_sq(fe *r, const fe *a) { fe_mul(r, a, a); }

/* r = a^(2^n) */
static void fe_sqn(fe *r, const fe *a, int n) {
    fe_copy(r, a);
    for (int i = 0; i < n; i++) fe_sq(r, r);
}

/* a^(2^252 - 3): shared chain for invert and sqrt (ref10 structure) */
static void fe_pow22523(fe *out, const fe *z) {
    fe t0, t1, t2;
    fe_sq(&t0, z);                 /* 2 */
    fe_sqn(&t1, &t0, 2);           /* 8 */
    fe_mul(&t1, z, &t1);           /* 9 */
    fe_mul(&t0, &t0, &t1);         /* 11 */
    fe_sq(&t0, &t0);               /* 22 */
    fe_mul(&t0, &t1, &t0);         /* 2^5 - 1 */
    fe_sqn(&t1, &t0, 5);
    fe_mul(&t0, &t1, &t0);         /* 2^10 - 1 */
    fe_sqn(&t1, &t0, 10);
    fe_mul(&t1, &t1, &t0);         /* 2^20 - 1 */
    fe_sqn(&t2, &t1, 20);
    fe_mul(&t1, &t2, &t1);         /* 2^40 - 1 */
    fe_sqn(&t1, &t1, 10);
    fe_mul(&t0, &t1, &t0);         /* 2^50 - 1 */
    fe_sqn(&t1, &t0, 50);
    fe_mul(&t1, &t1, &t0);         /* 2^100 - 1 */
    fe_sqn(&t2, &t1, 100);
    fe_mul(&t1, &t2, &t1);         /* 2^200 - 1 */
    fe_sqn(&t1, &t1, 50);
    fe_mul(&t0, &t1, &t0);         /* 2^250 - 1 */
    fe_sqn(&t0, &t0, 2);
    fe_mul(out, &t0, z);           /* 2^252 - 3 */
}

static void fe_invert(fe *out, const fe *z) {
    /* z^(p-2) = z^(2^255 - 21) via the classic chain */
    fe t0, t1, t2, t3;
    fe_sq(&t0, z);
    fe_sqn(&t1, &t0, 2);
    fe_mul(&t1, z, &t1);
    fe_mul(&t0, &t0, &t1);
    fe_sq(&t2, &t0);
    fe_mul(&t1, &t1, &t2);
    fe_sqn(&t2, &t1, 5);
    fe_mul(&t1, &t2, &t1);
    fe_sqn(&t2, &t1, 10);
    fe_mul(&t2, &t2, &t1);
    fe_sqn(&t3, &t2, 20);
    fe_mul(&t2, &t3, &t2);
    fe_sqn(&t2, &t2, 10);
    fe_mul(&t1, &t2, &t1);
    fe_sqn(&t2, &t1, 50);
    fe_mul(&t2, &t2, &t1);
    fe_sqn(&t3, &t2, 100);
    fe_mul(&t2, &t3, &t2);
    fe_sqn(&t2, &t2, 50);
    fe_mul(&t1, &t2, &t1);
    fe_sqn(&t1, &t1, 5);
    fe_mul(out, &t1, &t0);
}

static void fe_frombytes(fe *r, const uint8_t s[32]) {
    u64 w0, w1, w2, w3;
    memcpy(&w0, s, 8); memcpy(&w1, s + 8, 8);
    memcpy(&w2, s + 16, 8); memcpy(&w3, s + 24, 8);
    r->v[0] = w0 & MASK51;
    r->v[1] = ((w0 >> 51) | (w1 << 13)) & MASK51;
    r->v[2] = ((w1 >> 38) | (w2 << 26)) & MASK51;
    r->v[3] = ((w2 >> 25) | (w3 << 39)) & MASK51;
    r->v[4] = (w3 >> 12) & MASK51;
}

static void fe_tobytes(uint8_t s[32], const fe *a) {
    fe t = *a;
    fe_carry(&t);
    /* full reduction: add 19, fold, then subtract 2^255 bit */
    u64 q = (t.v[0] + 19) >> 51;
    q = (t.v[1] + q) >> 51;
    q = (t.v[2] + q) >> 51;
    q = (t.v[3] + q) >> 51;
    q = (t.v[4] + q) >> 51;
    t.v[0] += 19 * q;
    u64 c;
    c = t.v[0] >> 51; t.v[0] &= MASK51; t.v[1] += c;
    c = t.v[1] >> 51; t.v[1] &= MASK51; t.v[2] += c;
    c = t.v[2] >> 51; t.v[2] &= MASK51; t.v[3] += c;
    c = t.v[3] >> 51; t.v[3] &= MASK51; t.v[4] += c;
    t.v[4] &= MASK51;
    u64 w0 = t.v[0] | (t.v[1] << 51);
    u64 w1 = (t.v[1] >> 13) | (t.v[2] << 38);
    u64 w2 = (t.v[2] >> 26) | (t.v[3] << 25);
    u64 w3 = (t.v[3] >> 39) | (t.v[4] << 12);
    memcpy(s, &w0, 8); memcpy(s + 8, &w1, 8);
    memcpy(s + 16, &w2, 8); memcpy(s + 24, &w3, 8);
}

static int fe_isnegative(const fe *a) {
    uint8_t s[32];
    fe_tobytes(s, a);
    return s[0] & 1;
}

static int fe_iszero(const fe *a) {
    uint8_t s[32];
    static const uint8_t zero[32] = {0};
    fe_tobytes(s, a);
    return memcmp(s, zero, 32) == 0;
}

static int fe_eq(const fe *a, const fe *b) {
    fe d;
    fe_sub(&d, a, b);
    return fe_iszero(&d);
}

static void fe_neg(fe *r, const fe *a) {
    fe z;
    fe_zero(&z);
    fe_sub(r, &z, a);
}

static void fe_cabs(fe *r, const fe *a) {  /* |a| = -a if negative */
    if (fe_isnegative(a)) fe_neg(r, a); else fe_copy(r, a);
    fe_carry(r);
}

/* ---------------- curve constants ---------------- */

static fe FE_D, FE_SQRT_M1, FE_INVSQRT_A_MINUS_D, FE_ONE;

/* d = -121665/121666 */
static const uint8_t D_BYTES[32] = {
    0xa3,0x78,0x59,0x13,0xca,0x4d,0xeb,0x75,0xab,0xd8,0x41,0x41,
    0x4d,0x0a,0x70,0x00,0x98,0xe8,0x79,0x77,0x79,0x40,0xc7,0x8c,
    0x73,0xfe,0x6f,0x2b,0xee,0x6c,0x03,0x52};
static const uint8_t SQRT_M1_BYTES[32] = {
    0xb0,0xa0,0x0e,0x4a,0x27,0x1b,0xee,0xc4,0x78,0xe4,0x2f,0xad,
    0x06,0x18,0x43,0x2f,0xa7,0xd7,0xfb,0x3d,0x99,0x00,0x4d,0x2b,
    0x0b,0xdf,0xc1,0x4f,0x80,0x24,0x83,0x2b};

typedef struct { fe x, y, z, t; } ge;  /* extended coordinates, a=-1 */

static void ge_identity(ge *r) {
    fe_zero(&r->x); fe_one(&r->y); fe_one(&r->z); fe_zero(&r->t);
}

static void ge_add(ge *r, const ge *p, const ge *q) {
    fe a, b, c, d, e, f, g, h, t0, t1;
    fe_sub(&t0, &p->y, &p->x); fe_carry(&t0);
    fe_sub(&t1, &q->y, &q->x); fe_carry(&t1);
    fe_mul(&a, &t0, &t1);
    fe_add(&t0, &p->y, &p->x);
    fe_add(&t1, &q->y, &q->x);
    fe_mul(&b, &t0, &t1);
    fe_mul(&c, &p->t, &FE_D);
    fe_add(&c, &c, &c);
    fe_carry(&c);
    fe_mul(&c, &c, &q->t);
    fe_mul(&d, &p->z, &q->z);
    fe_add(&d, &d, &d);
    fe_sub(&e, &b, &a); fe_carry(&e);
    fe_sub(&f, &d, &c); fe_carry(&f);
    fe_add(&g, &d, &c); fe_carry(&g);
    fe_add(&h, &b, &a); fe_carry(&h);
    fe_mul(&r->x, &e, &f);
    fe_mul(&r->y, &g, &h);
    fe_mul(&r->z, &f, &g);
    fe_mul(&r->t, &e, &h);
}

/* RFC 9496 SQRT_RATIO_M1. Returns was_square; *r = sqrt(u/v) or sqrt(i*u/v), abs. */
static int sqrt_ratio_m1(fe *r, const fe *u, const fe *v) {
    fe v3, v7, t, check, u_neg, u_neg_i, rr;
    fe_sq(&v3, v); fe_mul(&v3, &v3, v);          /* v^3 */
    fe_sq(&v7, &v3); fe_mul(&v7, &v7, v);        /* v^7 */
    fe_mul(&t, u, &v7);
    fe_pow22523(&t, &t);                         /* (u v^7)^((p-5)/8) */
    fe_mul(&rr, u, &v3); fe_mul(&rr, &rr, &t);
    fe_sq(&check, &rr); fe_mul(&check, &check, v);
    fe_neg(&u_neg, u);
    fe_mul(&u_neg_i, &u_neg, &FE_SQRT_M1);
    int correct = fe_eq(&check, u);
    int flipped = fe_eq(&check, &u_neg);
    int flipped_i = fe_eq(&check, &u_neg_i);
    if (flipped || flipped_i) fe_mul(&rr, &rr, &FE_SQRT_M1);
    fe_cabs(r, &rr);
    return correct || flipped;
}

/* RFC 9496 decode; returns 0 ok, -1 invalid */
static int ristretto_decode(ge *p, const uint8_t s_bytes[32]) {
    fe s, ss, u1, u2, u2_sqr, v, t, den_x, den_y, x, y;
    /* canonical check: bytes must re-encode identically and be non-negative */
    fe_frombytes(&s, s_bytes);
    uint8_t chk[32];
    fe_tobytes(chk, &s);
    if (memcmp(chk, s_bytes, 32) != 0) return -1;
    if (s_bytes[0] & 1) return -1;

    fe_sq(&ss, &s);
    fe_one(&u1); fe_sub(&u1, &u1, &ss); fe_carry(&u1);      /* 1 - s^2 */
    fe_one(&u2); fe_add(&u2, &u2, &ss); fe_carry(&u2);      /* 1 + s^2 */
    fe_sq(&u2_sqr, &u2);
    fe_sq(&t, &u1); fe_mul(&t, &t, &FE_D);                  /* d u1^2 */
    fe_neg(&v, &t);
    fe_sub(&v, &v, &u2_sqr); fe_carry(&v);                  /* -(d u1^2) - u2^2 */
    fe mulv;
    fe_mul(&mulv, &v, &u2_sqr);
    fe one;
    fe_one(&one);
    int was_square = sqrt_ratio_m1(&t, &one, &mulv);        /* invsqrt */
    fe_mul(&den_x, &t, &u2);
    fe_mul(&den_y, &t, &den_x); fe_mul(&den_y, &den_y, &v);
    fe_add(&x, &s, &s);
    fe_mul(&x, &x, &den_x);
    fe_cabs(&x, &x);
    fe_mul(&y, &u1, &den_y);
    fe_mul(&t, &x, &y);
    if (!was_square || fe_isnegative(&t) || fe_iszero(&y)) return -1;
    fe_copy(&p->x, &x); fe_copy(&p->y, &y);
    fe_one(&p->z);
    fe_copy(&p->t, &t);
    return 0;
}

/* ristretto coset equality: X1 Y2 == Y1 X2  OR  Y1 Y2 == X1 X2 */
static int ristretto_eq(const ge *p, const ge *q) {
    fe a, b;
    fe_mul(&a, &p->x, &q->y);
    fe_mul(&b, &p->y, &q->x);
    if (fe_eq(&a, &b)) return 1;
    fe_mul(&a, &p->y, &q->y);
    fe_mul(&b, &p->x, &q->x);
    return fe_eq(&a, &b);
}

/* ---------------- fixed-base table ---------------- */

static const uint8_t BASEPOINT_BYTES[32] = {
    0xe2,0xf2,0xae,0x0a,0x6a,0xbc,0x4e,0x71,0xa8,0x84,0xa9,0x61,
    0xc5,0x00,0x51,0x5f,0x58,0xe3,0x0b,0x6a,0xa5,0x82,0xdd,0x8d,
    0xb6,0xa6,0x59,0x45,0xe0,0x8d,0x2d,0x76};

static ge FIXED_TABLE[64][16];
static int INITIALIZED = 0;

int r255_init(void) {
    if (INITIALIZED) return 0;
    fe_frombytes(&FE_D, D_BYTES);
    fe_frombytes(&FE_SQRT_M1, SQRT_M1_BYTES);
    fe_one(&FE_ONE);
    ge base;
    if (ristretto_decode(&base, BASEPOINT_BYTES) != 0) return -1;
    for (int w = 0; w < 64; w++) {
        ge_identity(&FIXED_TABLE[w][0]);
        for (int d = 1; d < 16; d++)
            ge_add(&FIXED_TABLE[w][d], &FIXED_TABLE[w][d - 1], &base);
        ge next;
        ge_add(&next, &FIXED_TABLE[w][1], &FIXED_TABLE[w][15]);  /* 16*base */
        base = next;
    }
    INITIALIZED = 1;
    return 0;
}

/* constant-time select: r = table[d] scanned with masks, no secret-
 * dependent branches or indices (the scalar is secret on the signing
 * path — r255_mult_base computes nonce*B and key*B) */
static void ge_ct_select(ge *r, const ge table[16], int d) {
    const u64 *src0 = (const u64 *)&table[0];
    u64 *dst = (u64 *)r;
    size_t words = sizeof(ge) / sizeof(u64);
    for (size_t i = 0; i < words; i++) dst[i] = src0[i];
    for (int j = 1; j < 16; j++) {
        u64 mask = (u64)0 - (u64)(((uint32_t)(j ^ d) - 1u) >> 31); /* all-1 iff j==d */
        const u64 *src = (const u64 *)&table[j];
        for (size_t i = 0; i < words; i++)
            dst[i] ^= mask & (dst[i] ^ src[i]);
    }
}

static void fixed_mult(ge *r, const uint8_t s[32]) {
    /* window 0 via select from identity-rooted table; remaining windows
     * always add (Edwards unified addition is complete, so adding the
     * selected entry — identity when the nibble is 0 — is safe) */
    ge t;
    ge_ct_select(r, FIXED_TABLE[0], s[0] & 0xF);
    for (int w = 1; w < 64; w++) {
        int d = (s[w >> 1] >> ((w & 1) * 4)) & 0xF;
        ge_ct_select(&t, FIXED_TABLE[w], d);
        ge_add(r, r, &t);
    }
}

/* Multi-scalar multiplication; scalars are 32-byte LE, verification-
 * only (variable time is fine — same stance as the Python path).
 *
 * Small n: Straus with per-point 4-bit tables (cheap setup).
 * Large n: Pippenger bucket method — per window of c bits, scatter
 * every point into one of 2^c-1 buckets (one add each), then fold the
 * buckets with the running-sum trick (2*(2^c-1) adds) and shift the
 * accumulator by c doublings. Total ≈ (256/c)*(n + 2^(c+1)) adds vs
 * Straus's ~74n: at n=4096 (a 2048-signature round, 2 points each)
 * that is ~2x fewer point additions, and the bucket scratch is O(2^c)
 * instead of Straus's n*16 table. */
#define STRAUS_MAX 64

/* ``tables``: n rows of 16, the caller's */
static void msm_straus(ge *out, size_t n, const ge *pts,
                       const uint8_t *scalars, ge (*tables)[16]) {
    for (size_t i = 0; i < n; i++) {
        ge_identity(&tables[i][0]);
        tables[i][1] = pts[i];
        for (int d = 2; d < 16; d++)
            ge_add(&tables[i][d], &tables[i][d - 1], &pts[i]);
    }
    ge acc;
    ge_identity(&acc);
    for (int w = 63; w >= 0; w--) {
        ge_add(&acc, &acc, &acc);
        ge_add(&acc, &acc, &acc);
        ge_add(&acc, &acc, &acc);
        ge_add(&acc, &acc, &acc);
        for (size_t i = 0; i < n; i++) {
            int d = (scalars[i * 32 + (w >> 1)] >> ((w & 1) * 4)) & 0xF;
            if (d) ge_add(&acc, &acc, &tables[i][d]);
        }
    }
    *out = acc;
}

/* c bits of a 32-byte LE scalar starting at bit position `bit` (c <= 8,
 * so two bytes always cover the window) */
static int scalar_window(const uint8_t *s, int bit, int c) {
    int byte = bit >> 3, shift = bit & 7;
    uint32_t v = s[byte];
    if (byte + 1 < 32) v |= (uint32_t)s[byte + 1] << 8;
    return (int)((v >> shift) & ((1u << c) - 1));
}

#define PIPPENGER_MAX_C 8
#define PIPPENGER_BUCKETS ((1 << PIPPENGER_MAX_C) - 1)

/* the window that costs the fewest additions for n points, from the
 * count above: ceil(256/c) windows of n scatters, 2*(2^c-1) folds and
 * c doublings. 4,096 points (one call for a round) take 8; the 400-600
 * of a chunk of a round spread over the host's cores take 6. */
static int pippenger_window(size_t n) {
    int best = 4;
    size_t best_cost = (size_t)-1;
    for (int c = 4; c <= PIPPENGER_MAX_C; c++) {
        size_t windows = (256 + c - 1) / c;
        size_t cost = windows * (n + 2 * ((1u << c) - 1) + c);
        if (cost < best_cost) { best_cost = cost; best = c; }
    }
    return best;
}

/* ``buckets``: PIPPENGER_BUCKETS of them, the caller's */
static void msm_pippenger(ge *out, size_t n, const ge *pts,
                          const uint8_t *scalars, ge *buckets) {
    int c = pippenger_window(n);
    int nbuckets = (1 << c) - 1;
    int windows = (256 + c - 1) / c;
    ge acc;
    ge_identity(&acc);
    for (int w = windows - 1; w >= 0; w--) {
        for (int j = 0; j < c; j++) ge_add(&acc, &acc, &acc);
        for (int j = 0; j < nbuckets; j++) ge_identity(&buckets[j]);
        int bit = w * c;
        for (size_t i = 0; i < n; i++) {
            int d = scalar_window(scalars + 32 * i, bit, c);
            if (d) ge_add(&buckets[d - 1], &buckets[d - 1], &pts[i]);
        }
        /* sum_d d*bucket[d] = sum of suffix running sums */
        ge sum, runsum;
        ge_identity(&sum);
        ge_identity(&runsum);
        for (int j = nbuckets - 1; j >= 0; j--) {
            ge_add(&runsum, &runsum, &buckets[j]);
            ge_add(&sum, &sum, &runsum);
        }
        ge_add(&acc, &acc, &sum);
    }
    *out = acc;
}

/* how many ``ge`` of scratch msm() needs for n points */
static size_t msm_scratch(size_t n) {
    return n <= STRAUS_MAX ? n * 16 : PIPPENGER_BUCKETS;
}

static void msm(ge *out, size_t n, const ge *pts, const uint8_t *scalars,
                ge *scratch) {
    if (n <= STRAUS_MAX)
        msm_straus(out, n, pts, scalars, (ge (*)[16])scratch);
    else
        msm_pippenger(out, n, pts, scalars, scratch);
}

/* Decoded-public-key cache: ristretto decode costs one field
 * exponentiation (~15-19 us on a weak core) and the batch equation
 * decodes TWO points per signature — but the A_i are client identity
 * keys, which repeat heavily across a session's requests, while the
 * R_i are fresh nonce points every time. Direct-mapped, keyed by the
 * full 32-byte encoding; stores only successfully-decoded canonical
 * points, so a hit is exactly equivalent to a fresh decode. Its one
 * caller, r255_verify1, runs under the Python wrapper's module lock,
 * which serializes all access to this static table; the chunk check
 * never touches it (it decodes each distinct key of its chunk once). */
#define PUBCACHE_BITS 13
#define PUBCACHE_N (1 << PUBCACHE_BITS)
static struct { uint8_t key[32]; ge val; uint8_t full; } pubcache[PUBCACHE_N];

static int ristretto_decode_pub(ge *out, const uint8_t enc[32]) {
    uint64_t h;
    memcpy(&h, enc, 8);
    uint32_t slot = (uint32_t)(h ^ (h >> 17) ^ (h >> 31)) & (PUBCACHE_N - 1);
    if (pubcache[slot].full && memcmp(pubcache[slot].key, enc, 32) == 0) {
        *out = pubcache[slot].val;
        return 0;
    }
    if (ristretto_decode(out, enc) != 0) return -1;
    memcpy(pubcache[slot].key, enc, 32);
    pubcache[slot].val = *out;
    pubcache[slot].full = 1;
    return 0;
}

/* ---------------- exported checks ---------------- */

/* s*B == R + k*A; all inputs 32-byte LE. 1 valid, 0 invalid, -1 bad input */
int r255_verify1(const uint8_t pub[32], const uint8_t r_enc[32],
                 const uint8_t s[32], const uint8_t k[32]) {
    if (r255_init() != 0) return -1;
    ge a_pt, big_r, left, right;
    if (ristretto_decode_pub(&a_pt, pub) != 0) return -1;
    if (ristretto_decode(&big_r, r_enc) != 0) return -1;
    fixed_mult(&left, s);
    ge tables[16];
    msm(&right, 1, &a_pt, k, tables);
    ge_add(&right, &right, &big_r);
    return ristretto_eq(&left, &right);
}

/* RFC 9496 encode of an internal point */
static void ristretto_encode_ge(uint8_t out[32], const ge *pp) {
    ge p = *pp;
    fe u1, u2, t, den1, den2, z_inv, ix0, iy0, enchanted, x, y, den_inv, s_out;
    fe_add(&u1, &p.z, &p.y);
    fe_sub(&t, &p.z, &p.y); fe_carry(&t);
    fe_mul(&u1, &u1, &t);
    fe_mul(&u2, &p.x, &p.y);
    fe u2sq, mulv;
    fe_sq(&u2sq, &u2);
    fe_mul(&mulv, &u1, &u2sq);
    fe one;
    fe_one(&one);
    fe invsqrt;
    sqrt_ratio_m1(&invsqrt, &one, &mulv);
    fe_mul(&den1, &invsqrt, &u1);
    fe_mul(&den2, &invsqrt, &u2);
    fe_mul(&z_inv, &den1, &den2);
    fe_mul(&z_inv, &z_inv, &p.t);
    fe_mul(&ix0, &p.x, &FE_SQRT_M1);
    fe_mul(&iy0, &p.y, &FE_SQRT_M1);
    /* INVSQRT_A_MINUS_D = 1/sqrt(a-d) with a=-1: sqrt_ratio(1, -1-d) */
    fe amd;
    fe_one(&amd);
    fe_neg(&amd, &amd);
    fe_sub(&amd, &amd, &FE_D); fe_carry(&amd);
    sqrt_ratio_m1(&enchanted, &one, &amd);
    fe_mul(&enchanted, &den1, &enchanted);
    fe tz;
    fe_mul(&tz, &p.t, &z_inv);
    int rotate = fe_isnegative(&tz);
    if (rotate) {
        fe_copy(&x, &iy0); fe_copy(&y, &ix0); fe_copy(&den_inv, &enchanted);
    } else {
        fe_copy(&x, &p.x); fe_copy(&y, &p.y); fe_copy(&den_inv, &den2);
    }
    fe xz;
    fe_mul(&xz, &x, &z_inv);
    if (fe_isnegative(&xz)) fe_neg(&y, &y);
    fe_sub(&t, &p.z, &y); fe_carry(&t);
    fe_mul(&s_out, &den_inv, &t);
    fe_cabs(&s_out, &s_out);
    fe_tobytes(out, &s_out);
}

/* test hook: decode+re-encode (canonicality / round-trip checks) */
int r255_encode(uint8_t out[32], const uint8_t in[32]) {
    if (r255_init() != 0) return -1;
    ge p;
    if (ristretto_decode(&p, in) != 0) return -1;
    ristretto_encode_ge(out, &p);
    return 0;
}

/* out = s*B (fixed-base, for client-side signing). 0 ok, -1 init fail */
int r255_mult_base(uint8_t out[32], const uint8_t s[32]) {
    if (r255_init() != 0) return -1;
    ge p;
    fixed_mult(&p, s);
    ristretto_encode_ge(out, &p);
    return 0;
}

/* ------------------------------------------------------------------ */
/* Keccak-f[1600] (FIPS 202 permutation), for the merlin/STROBE layer  */
/* under sr25519 signatures (session/merlin.py).  The pure-Python      */
/* permutation costs ~10^2 us; per-request signature verification runs */
/* several permutations, so the hot path dispatches here when loaded.  */
/* State: 200 bytes, 25 little-endian u64 lanes.                       */
/* ------------------------------------------------------------------ */

static const uint64_t keccak_rc[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL,
};

static const int keccak_rot[25] = {
    0, 1, 62, 28, 27, 36, 44, 6, 55, 20, 3, 10, 43, 25, 39,
    41, 45, 15, 21, 8, 18, 2, 61, 56, 14,
};

static uint64_t rotl64(uint64_t v, int n) {
    return n == 0 ? v : (v << n) | (v >> (64 - n));
}

void r255_keccak_f1600(uint8_t state[200]) {
    uint64_t a[25];
    for (int i = 0; i < 25; i++) {
        uint64_t v = 0;
        for (int j = 7; j >= 0; j--) v = (v << 8) | state[8 * i + j];
        a[i] = v;
    }
    for (int round = 0; round < 24; round++) {
        uint64_t c[5], d[5], b[25];
        for (int x = 0; x < 5; x++)
            c[x] = a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20];
        for (int x = 0; x < 5; x++)
            d[x] = c[(x + 4) % 5] ^ rotl64(c[(x + 1) % 5], 1);
        for (int x = 0; x < 5; x++)
            for (int y = 0; y < 25; y += 5) a[x + y] ^= d[x];
        for (int x = 0; x < 5; x++)
            for (int y = 0; y < 5; y++)
                b[y + 5 * ((2 * x + 3 * y) % 5)] =
                    rotl64(a[x + 5 * y], keccak_rot[x + 5 * y]);
        for (int x = 0; x < 5; x++)
            for (int y = 0; y < 25; y += 5)
                a[x + y] = b[x + y] ^ (~b[(x + 1) % 5 + y] & b[(x + 2) % 5 + y]);
        a[0] ^= keccak_rc[round];
    }
    for (int i = 0; i < 25; i++) {
        uint64_t v = a[i];
        for (int j = 0; j < 8; j++) { state[8 * i + j] = (uint8_t)v; v >>= 8; }
    }
}

/* ------------------------------------------------------------------ */
/* STROBE-128 duplex (the trimmed subset merlin embeds) over the      */
/* permutation above — the per-request signature hot path runs ~8     */
/* transcript ops per challenge derivation, and the Python framing    */
/* (session/merlin.py) costs ~85 us/challenge; these C ops cut that   */
/* to single-digit us. Layout: one 203-byte blob shared with Python:  */
/*   [0..200) keccak state | [200] pos | [201] pos_begin | [202] cur_flags */
/* merlin.py's pure-Python Strobe128 is the correctness oracle.       */
/* ------------------------------------------------------------------ */

#define STROBE_R 166
#define SF_I 1
#define SF_A 2
#define SF_C 4
#define SF_T 8
#define SF_M 16
#define SF_K 32

static void strobe_run_f(uint8_t *b) {
    b[b[200]] ^= b[201];
    b[b[200] + 1] ^= 0x04;
    b[STROBE_R + 1] ^= 0x80;
    r255_keccak_f1600(b);
    b[200] = 0;
    b[201] = 0;
}

static void strobe_absorb(uint8_t *b, const uint8_t *d, size_t n) {
    uint8_t pos = b[200];
    for (size_t i = 0; i < n; i++) {
        b[pos++] ^= d[i];
        if (pos == STROBE_R) {
            b[200] = pos;
            strobe_run_f(b);
            pos = 0;
        }
    }
    b[200] = pos;
}

static void strobe_overwrite(uint8_t *b, const uint8_t *d, size_t n) {
    uint8_t pos = b[200];
    for (size_t i = 0; i < n; i++) {
        b[pos++] = d[i];
        if (pos == STROBE_R) {
            b[200] = pos;
            strobe_run_f(b);
            pos = 0;
        }
    }
    b[200] = pos;
}

static void strobe_squeeze(uint8_t *b, uint8_t *out, size_t n) {
    uint8_t pos = b[200];
    for (size_t i = 0; i < n; i++) {
        out[i] = b[pos];
        b[pos++] = 0;
        if (pos == STROBE_R) {
            b[200] = pos;
            strobe_run_f(b);
            pos = 0;
        }
    }
    b[200] = pos;
}

static int strobe_begin_op(uint8_t *b, uint8_t flags, int more) {
    if (more) return flags == b[202] ? 0 : -1;
    if (flags & SF_T) return -2;
    uint8_t header[2];
    header[0] = b[201];           /* old pos_begin */
    header[1] = flags;
    b[201] = b[200] + 1;
    b[202] = flags;
    strobe_absorb(b, header, 2);
    if ((flags & (SF_C | SF_K)) && b[200] != 0) strobe_run_f(b);
    return 0;
}

/* op: 0 = meta_ad, 1 = ad, 2 = prf (data unused, out filled), 3 = key */
int r255_strobe_op(uint8_t *b, int op, const uint8_t *data, size_t n,
                   uint8_t *out, int more) {
    int rc;
    switch (op) {
    case 0:
        rc = strobe_begin_op(b, SF_M | SF_A, more);
        if (rc) return rc;
        strobe_absorb(b, data, n);
        return 0;
    case 1:
        rc = strobe_begin_op(b, SF_A, more);
        if (rc) return rc;
        strobe_absorb(b, data, n);
        return 0;
    case 2:
        rc = strobe_begin_op(b, SF_I | SF_A | SF_C, more);
        if (rc) return rc;
        strobe_squeeze(b, out, n);
        return 0;
    case 3:
        rc = strobe_begin_op(b, SF_A | SF_C, more);
        if (rc) return rc;
        strobe_overwrite(b, data, n);
        return 0;
    }
    return -3;
}

/* merlin append_message: meta_ad(label) ‖ meta_ad(LE32(len), more) ‖ ad(msg)
   — one library crossing instead of three (transcript.rs framing). */
void r255_merlin_append(uint8_t *b, const uint8_t *label, size_t llen,
                        const uint8_t *msg, size_t mlen) {
    uint8_t le[4] = {(uint8_t)mlen, (uint8_t)(mlen >> 8),
                     (uint8_t)(mlen >> 16), (uint8_t)(mlen >> 24)};
    strobe_begin_op(b, SF_M | SF_A, 0);
    strobe_absorb(b, label, llen);
    strobe_absorb(b, le, 4);
    strobe_begin_op(b, SF_A, 0);
    strobe_absorb(b, msg, mlen);
}

/* merlin challenge_bytes: meta_ad(label) ‖ meta_ad(LE32(n), more) ‖ PRF(n). */
void r255_merlin_challenge(uint8_t *b, const uint8_t *label, size_t llen,
                           uint8_t *out, size_t n) {
    uint8_t le[4] = {(uint8_t)n, (uint8_t)(n >> 8), (uint8_t)(n >> 16),
                     (uint8_t)(n >> 24)};
    strobe_begin_op(b, SF_M | SF_A, 0);
    strobe_absorb(b, label, llen);
    strobe_absorb(b, le, 4);
    strobe_begin_op(b, SF_I | SF_A | SF_C, 0);
    strobe_squeeze(b, out, n);
}

/* The full schnorrkel Fiat–Shamir challenge in one crossing: clone the
   cached SigningContext prefix (203-byte blob), absorb the message and
   the sign.rs label sequence, squeeze 64 challenge bytes. Labels are
   schnorrkel-og 0.11 sign.rs/context.rs; session/merlin.py's Python
   framing is the oracle (tests/test_merlin.py equivalence). */
void r255_schnorrkel_challenge(const uint8_t *prefix_blob,
                               const uint8_t *msg, size_t mlen,
                               const uint8_t *pub, const uint8_t *r_enc,
                               uint8_t *out64) {
    uint8_t b[203];
    memcpy(b, prefix_blob, 203);
    r255_merlin_append(b, (const uint8_t *)"sign-bytes", 10, msg, mlen);
    r255_merlin_append(b, (const uint8_t *)"proto-name", 10,
                       (const uint8_t *)"Schnorr-sig", 11);
    r255_merlin_append(b, (const uint8_t *)"sign:pk", 7, pub, 32);
    r255_merlin_append(b, (const uint8_t *)"sign:R", 6, r_enc, 32);
    r255_merlin_challenge(b, (const uint8_t *)"sign:c", 6, out64, 64);
}

/* ------------------------------------------------------------------ */
/* The chunk check: everything one batch equation needs, in one        */
/* crossing and with no shared scratch, so the scheduler can run a     */
/* round's chunks on several threads at once (server/scheduler.py).    */
/* ------------------------------------------------------------------ */

/* Scalars mod L = 2^252 + SC_C as little-endian u64 limbs. */
static const u64 SC_L[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL,
                            0, 0x1000000000000000ULL};
static const u64 SC_2L[4] = {0xb024c634b9eba7daULL, 0x29bdf3bd45ef39acULL,
                             0, 0x2000000000000000ULL};
static const u64 SC_C[2] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL};

static void sc_add4(u64 r[4], const u64 a[4]) {
    u128 carry = 0;
    for (int i = 0; i < 4; i++) {
        carry += (u128)r[i] + a[i];
        r[i] = (u64)carry;
        carry >>= 64;
    }
}

static void sc_sub4(u64 r[4], const u64 a[4]) {  /* r >= a */
    u64 borrow = 0;
    for (int i = 0; i < 4; i++) {
        u64 d = r[i] - a[i], b = r[i] < a[i];
        r[i] = d - borrow;
        borrow = b | (d < borrow);
    }
}

static int sc_geq4(const u64 a[4], const u64 b[4]) {
    for (int i = 3; i >= 0; i--)
        if (a[i] != b[i]) return a[i] > b[i];
    return 1;
}

/* out[na+nb] = a[na] * b[nb] */
static void sc_mul(u64 *out, const u64 *a, int na, const u64 *b, int nb) {
    memset(out, 0, (size_t)(na + nb) * sizeof(u64));
    for (int i = 0; i < na; i++) {
        u64 carry = 0;
        for (int j = 0; j < nb; j++) {
            u128 t = (u128)a[i] * b[j] + out[i + j] + carry;
            out[i + j] = (u64)t;
            carry = (u64)(t >> 64);
        }
        out[i + nb] = carry;
    }
}

/* r = x mod L for x of nx <= 8 limbs. 2^252 = -SC_C (mod L), so
 * x = lo + hi*2^252 = lo - SC_C*hi, and SC_C*hi is 125 bits shorter
 * than x: fold until hi is 0 (four times for 512 bits), summing the
 * low parts that came with a plus sign and those with a minus sign
 * apart (each sum under 2^254), and subtract once at the end. */
static void sc_reduce(u64 r[4], const u64 *x, int nx) {
    u64 cur[8], hi[5], acc[2][4] = {{0}, {0}};
    int n = nx, sign = 0;
    memcpy(cur, x, (size_t)nx * sizeof(u64));
    for (;;) {
        u64 lo[4] = {0, 0, 0, 0};
        for (int i = 0; i < 4 && i < n; i++) lo[i] = cur[i];
        lo[3] &= 0x0FFFFFFFFFFFFFFFULL;
        sc_add4(acc[sign], lo);
        int nh = n - 3;
        u64 any = 0;
        for (int i = 0; i < nh; i++) {
            hi[i] = cur[i + 3] >> 60;
            if (i + 4 < n) hi[i] |= cur[i + 4] << 4;
            any |= hi[i];
        }
        if (nh <= 0 || !any) break;
        sc_mul(cur, SC_C, 2, hi, nh);
        n = nh + 2;
        sign ^= 1;
    }
    memcpy(r, acc[0], sizeof acc[0]);
    sc_add4(r, SC_2L);          /* acc[1] < 2^252 + 2^132 < 2L */
    sc_sub4(r, acc[1]);
    while (sc_geq4(r, SC_L)) sc_sub4(r, SC_L);
}

/* A chunk's scalars: for item i, scal[64i..] = z_i (the 128 random
 * bits, forced odd) and scal[64i+32..] = z_i*k_i mod L; sb = sum
 * z_i*s_i mod L. With ``prefix_blob`` (sr25519) a signature must carry
 * schnorrkel's marker bit, which is cleared before the range check,
 * and k_i is the merlin challenge over (message, key, R); without it
 * (the RFC 9496 scheme hashes in Python) k_i is ks[32i..]. 0, or -1 for
 * a signature that cannot be one (no marker, s >= L). */
static int chunk_scalars(size_t n, const uint8_t *pubs, const uint8_t *sigs,
                         const uint8_t *rand16, const uint8_t *prefix_blob,
                         const uint8_t *msgs, const uint32_t *mlens,
                         const uint8_t *ks, uint8_t *scal, uint8_t sb[32]) {
    u64 sum[8] = {0}, prod[8], s[4], k[4], z[2], wide[8];
    for (size_t i = 0; i < n; i++) {
        const uint8_t *sig = sigs + 64 * i, *pub = pubs + 32 * i;
        memcpy(s, sig + 32, 32);
        if (prefix_blob) {
            if (!(s[3] >> 63)) return -1;
            s[3] &= 0x7FFFFFFFFFFFFFFFULL;
        }
        if (sc_geq4(s, SC_L)) return -1;
        if (prefix_blob) {
            uint8_t out64[64];
            r255_schnorrkel_challenge(prefix_blob, msgs, mlens[i], pub, sig,
                                      out64);
            msgs += mlens[i];
            memcpy(wide, out64, 64);
            sc_reduce(k, wide, 8);
        } else {
            memcpy(k, ks + 32 * i, 32);
        }
        memcpy(z, rand16 + 16 * i, 16);
        z[0] |= 1;
        sc_mul(prod, z, 2, k, 4);
        sc_reduce(k, prod, 6);
        memset(scal + 64 * i, 0, 32);
        memcpy(scal + 64 * i, z, 16);
        memcpy(scal + 64 * i + 32, k, 32);
        /* sum of n <= 2^16 products under 2^381 stays under 2^397 */
        sc_mul(prod, z, 2, s, 4);
        u128 carry = 0;
        for (int j = 0; j < 8; j++) {
            carry += (u128)sum[j] + (j < 6 ? prod[j] : 0);
            sum[j] = (u64)carry;
            carry >>= 64;
        }
    }
    sc_reduce(s, sum, 8);
    memcpy(sb, s, 32);
    return 0;
}

#define CHUNK_MAX 65536

/* test hook: the scalars alone (tests/test_native_r255.py holds them
 * to Python's big integers and to the golden challenge) */
int r255_chunk_scalars(size_t n, const uint8_t *pubs, const uint8_t *sigs,
                       const uint8_t *rand16, const uint8_t *prefix_blob,
                       const uint8_t *msgs, const uint32_t *mlens,
                       const uint8_t *ks, uint8_t *scal, uint8_t sb[32]) {
    if (n > CHUNK_MAX || (!prefix_blob && !ks)) return -1;
    return chunk_scalars(n, pubs, sigs, rand16, prefix_blob, msgs, mlens, ks,
                         scal, sb);
}

/* One random-linear-combination equation over n signatures:
 *   pubs n*32, sigs n*64 (R ‖ s), rand16 n*16 unpredictable bytes,
 *   then either prefix_blob (203 B) + msgs (concatenated) + mlens, or ks.
 * 1 every signature verifies, 0 the equation fails, -1 malformed input
 * (an s out of range, an encoding that is no point), -2 no memory. */
static int chunk_check(size_t n, const uint8_t *pubs, const uint8_t *sigs,
                       const uint8_t *rand16, const uint8_t *prefix_blob,
                       const uint8_t *msgs, const uint32_t *mlens,
                       const uint8_t *ks) {
    if (!INITIALIZED || n > CHUNK_MAX || (!prefix_blob && !ks)) return -1;
    if (n == 0) return 1;
    /* the call's own arena: points, MSM scratch, scalars, and the table
     * that finds a public key this chunk has already decoded */
    size_t npts = 2 * n, nslots = 4;
    while (nslots < npts) nslots <<= 1;
    size_t nscratch = msm_scratch(npts);
    uint8_t *arena = malloc((npts + nscratch) * sizeof(ge) + npts * 32
                            + nslots * sizeof(int32_t));
    if (!arena) return -2;
    ge *pts = (ge *)arena, *scratch = pts + npts;
    uint8_t *scal = (uint8_t *)(scratch + nscratch), sb[32];
    int32_t *slots = (int32_t *)(scal + npts * 32);
    int rc = -1;
    if (chunk_scalars(n, pubs, sigs, rand16, prefix_blob, msgs, mlens, ks,
                      scal, sb) != 0)
        goto done;
    memset(slots, 0xFF, nslots * sizeof(int32_t));
    for (size_t i = 0; i < n; i++) {
        const uint8_t *pub = pubs + 32 * i;
        if (ristretto_decode(&pts[2 * i], sigs + 64 * i) != 0) goto done;
        uint64_t h;
        memcpy(&h, pub, 8);
        size_t slot = (size_t)(h ^ (h >> 17) ^ (h >> 31)) & (nslots - 1);
        while (slots[slot] >= 0 && memcmp(pubs + 32 * slots[slot], pub, 32))
            slot = (slot + 1) & (nslots - 1);
        if (slots[slot] >= 0) {
            pts[2 * i + 1] = pts[2 * slots[slot] + 1];
        } else {
            if (ristretto_decode(&pts[2 * i + 1], pub) != 0) goto done;
            slots[slot] = (int32_t)i;
        }
    }
    ge left, right;
    fixed_mult(&left, sb);
    msm(&right, npts, pts, scal, scratch);
    rc = ristretto_eq(&left, &right);
done:
    free(arena);
    return rc;
}

/* A round's first pass: the n items as k contiguous chunks of
 * ceil(n/k), each its own equation (chunk_check), k-1 of them on
 * threads that live for this call and one on the caller's. The caller
 * crosses into C once and holds no GIL meanwhile, so a chunk waits for
 * a core and for nothing else: handing chunks to interpreter threads
 * instead made each wait twice for the GIL (PERF.md, PR 29: 12 ms for
 * 2,048 signatures as 8 chunks on an idle host, 77 beside one busy
 * Python thread). 1 when every chunk verifies, else the first chunk's
 * answer that is not 1. */
#define ROUND_MAX_CHUNKS 64

typedef struct {
    size_t n;
    const uint8_t *pubs, *sigs, *rand16, *prefix_blob, *msgs, *ks;
    const uint32_t *mlens;
    int rc;
} chunk_job;

static void *chunk_job_run(void *arg) {
    chunk_job *j = arg;
    j->rc = chunk_check(j->n, j->pubs, j->sigs, j->rand16, j->prefix_blob,
                        j->msgs, j->mlens, j->ks);
    return NULL;
}

static int round_check(size_t n, size_t k, const uint8_t *pubs,
                       const uint8_t *sigs, const uint8_t *rand16,
                       const uint8_t *prefix_blob, const uint8_t *msgs,
                       const uint32_t *mlens, const uint8_t *ks) {
    if (!prefix_blob && !ks) return -1;
    if (k < 1) k = 1;
    if (k > ROUND_MAX_CHUNKS) k = ROUND_MAX_CHUNKS;
    chunk_job jobs[ROUND_MAX_CHUNKS];
    pthread_t threads[ROUND_MAX_CHUNKS];
    int started[ROUND_MAX_CHUNKS] = {0};
    size_t step = (n + k - 1) / k, njobs = 0;
    for (size_t i = 0; i < n; i += step, njobs++) {
        chunk_job *j = &jobs[njobs];
        j->n = n - i < step ? n - i : step;
        j->pubs = pubs + 32 * i;
        j->sigs = sigs + 64 * i;
        j->rand16 = rand16 + 16 * i;
        j->prefix_blob = prefix_blob;
        j->msgs = msgs;
        j->mlens = prefix_blob ? mlens + i : NULL;
        j->ks = ks ? ks + 32 * i : NULL;
        j->rc = -1;
        if (prefix_blob)
            for (size_t m = 0; m < j->n; m++) msgs += mlens[i + m];
    }
    /* the chunk threads take no signals: the interpreter's handlers
     * belong on its own threads */
    sigset_t all, old;
    sigfillset(&all);
    pthread_sigmask(SIG_SETMASK, &all, &old);
    for (size_t c = 1; c < njobs; c++)
        started[c] = pthread_create(&threads[c], NULL, chunk_job_run,
                                    &jobs[c]) == 0;
    pthread_sigmask(SIG_SETMASK, &old, NULL);
    for (size_t c = 0; c < njobs; c++) {
        if (started[c]) pthread_join(threads[c], NULL);
        else chunk_job_run(&jobs[c]);  /* the caller's own, or no thread */
    }
    for (size_t c = 0; c < njobs; c++)
        if (jobs[c].rc != 1) return jobs[c].rc;
    return 1;
}

/* The round's check, and through elapsed_s (NULL: not wanted) the
 * seconds it took by its own reading of CLOCK_MONOTONIC (the clock of
 * Python's perf_counter on Linux): what the caller's stamps around the
 * crossing read beyond this is the wait to get the GIL back
 * (obs/phases.py, span verify_native). */
int r255_round_check(size_t n, size_t k, const uint8_t *pubs,
                     const uint8_t *sigs, const uint8_t *rand16,
                     const uint8_t *prefix_blob, const uint8_t *msgs,
                     const uint32_t *mlens, const uint8_t *ks,
                     double *elapsed_s) {
    struct timespec a, b;
    clock_gettime(CLOCK_MONOTONIC, &a);
    int rc = round_check(n, k, pubs, sigs, rand16, prefix_blob, msgs, mlens,
                         ks);
    clock_gettime(CLOCK_MONOTONIC, &b);
    if (elapsed_s)
        *elapsed_s = (double)(b.tv_sec - a.tv_sec)
                     + (double)(b.tv_nsec - a.tv_nsec) * 1e-9;
    return rc;
}


/* ---- ChaCha20 (RFC 7539), the seal's stream cipher --------------------
 *
 * engine/checkpoint.py keeps the numpy version as the plain reference
 * and pins this one to it byte for byte (tests/test_seal_stream.py).
 * Eight blocks a pass, one per lane of a 256-bit vector, written with
 * the compiler's vector extension: with AVX2 one instruction a step,
 * without it two SSE2 ones (the build has no -march, so the AVX2 body
 * is a clone the loader picks on a CPU that has it). */

typedef uint32_t v8u __attribute__((vector_size(32)));

#define CC_ROT(v, n) (((v) << (n)) | ((v) >> (32 - (n))))
#define CC_QR(a, b, c, d)                                             \
    a += b; d ^= a; d = CC_ROT(d, 16);                                \
    c += d; b ^= c; b = CC_ROT(b, 12);                                \
    a += b; d ^= a; d = CC_ROT(d, 8);                                 \
    c += d; b ^= c; b = CC_ROT(b, 7);

static uint32_t load32_le(const uint8_t *p) {
    return (uint32_t)p[0] | (uint32_t)p[1] << 8 | (uint32_t)p[2] << 16
           | (uint32_t)p[3] << 24;
}

/* the keystream of blocks counter .. counter+7, 512 bytes, block after
 * block; the counter wraps at 2^32 as the reference's does */
#if defined(__x86_64__) && defined(__linux__) && defined(__GNUC__) \
    && !defined(__clang__)
__attribute__((target_clones("avx2", "default")))
#endif
static void chacha20_ks8(const uint32_t key[8], const uint32_t nonce[3],
                         uint32_t counter, uint8_t out[512]) {
    static const uint32_t sigma[4] = {0x61707865, 0x3320646e, 0x79622d32,
                                      0x6b206574};
    v8u init[16], x[16];
    for (int i = 0; i < 4; i++) init[i] = (v8u){0} + sigma[i];
    for (int i = 0; i < 8; i++) init[4 + i] = (v8u){0} + key[i];
    init[12] = (v8u){0, 1, 2, 3, 4, 5, 6, 7} + counter;
    for (int i = 0; i < 3; i++) init[13 + i] = (v8u){0} + nonce[i];
    for (int i = 0; i < 16; i++) x[i] = init[i];
    for (int r = 0; r < 10; r++) {
        CC_QR(x[0], x[4], x[8], x[12])
        CC_QR(x[1], x[5], x[9], x[13])
        CC_QR(x[2], x[6], x[10], x[14])
        CC_QR(x[3], x[7], x[11], x[15])
        CC_QR(x[0], x[5], x[10], x[15])
        CC_QR(x[1], x[6], x[11], x[12])
        CC_QR(x[2], x[7], x[8], x[13])
        CC_QR(x[3], x[4], x[9], x[14])
    }
    for (int i = 0; i < 16; i++) {
        x[i] += init[i];
        for (int lane = 0; lane < 8; lane++) {
            uint32_t w = x[i][lane];
            uint8_t *o = out + 64 * lane + 4 * i;
            o[0] = (uint8_t)w; o[1] = (uint8_t)(w >> 8);
            o[2] = (uint8_t)(w >> 16); o[3] = (uint8_t)(w >> 24);
        }
    }
}

typedef struct {
    const uint32_t *key, *nonce;
    uint32_t counter;
    const uint8_t *src;
    uint8_t *dst;
    size_t n;
} chacha_job;

static void *chacha_job_run(void *arg) {
    chacha_job *j = arg;
    uint8_t ks[512];
    uint32_t counter = j->counter;
    for (size_t off = 0; off < j->n; off += 512, counter += 8) {
        size_t m = j->n - off < 512 ? j->n - off : 512;
        chacha20_ks8(j->key, j->nonce, counter, ks);
        const uint8_t *s = j->src + off;
        uint8_t *d = j->dst + off;
        size_t i = 0;
        for (; i + 8 <= m; i += 8) {  /* words: dst may be src */
            uint64_t a, b;
            memcpy(&a, s + i, 8);
            memcpy(&b, ks + i, 8);
            a ^= b;
            memcpy(d + i, &a, 8);
        }
        for (; i < m; i++) d[i] = s[i] ^ ks[i];
    }
    return NULL;
}

#define CHACHA_MAX_THREADS 16
/* below this a part is not worth a thread of its own */
#define CHACHA_MIN_PART (1u << 20)

/* dst[0..n) = src[0..n) XOR keystream(key, nonce) from block `counter`
 * on. dst may be src; otherwise the two do not overlap. The bytes are
 * cut into at most `threads` parts of whole 512-byte passes, one on the
 * caller's thread and the rest on threads that live for this call, as
 * round_check's are. -1 when the counter would pass 2^32 (a keystream
 * block met twice), 0 otherwise. */
int r255_chacha20_xor(const uint8_t key[32], const uint8_t nonce[12],
                      uint64_t counter, const uint8_t *src, uint8_t *dst,
                      size_t n, size_t threads) {
    uint32_t k[8], nc[3];
    if (counter + (n + 63) / 64 > ((uint64_t)1 << 32)) return -1;
    for (int i = 0; i < 8; i++) k[i] = load32_le(key + 4 * i);
    for (int i = 0; i < 3; i++) nc[i] = load32_le(nonce + 4 * i);
    if (threads < 1) threads = 1;
    if (threads > CHACHA_MAX_THREADS) threads = CHACHA_MAX_THREADS;
    if (threads > n / CHACHA_MIN_PART) threads = n / CHACHA_MIN_PART;
    if (threads < 1) threads = 1;
    size_t step = ((n + threads - 1) / threads + 511) / 512 * 512;
    chacha_job jobs[CHACHA_MAX_THREADS];
    pthread_t tids[CHACHA_MAX_THREADS];
    int started[CHACHA_MAX_THREADS] = {0};
    size_t njobs = 0;
    for (size_t off = 0; off < n; off += step, njobs++) {
        chacha_job *j = &jobs[njobs];
        j->key = k; j->nonce = nc;
        j->counter = (uint32_t)(counter + off / 64);
        j->src = src + off; j->dst = dst + off;
        j->n = n - off < step ? n - off : step;
    }
    sigset_t all, old;
    sigfillset(&all);
    pthread_sigmask(SIG_SETMASK, &all, &old);
    for (size_t c = 1; c < njobs; c++)
        started[c] = pthread_create(&tids[c], NULL, chacha_job_run,
                                    &jobs[c]) == 0;
    pthread_sigmask(SIG_SETMASK, &old, NULL);
    for (size_t c = 0; c < njobs; c++) {
        if (started[c]) pthread_join(tids[c], NULL);
        else chacha_job_run(&jobs[c]);  /* the caller's own, or no thread */
    }
    return 0;
}
