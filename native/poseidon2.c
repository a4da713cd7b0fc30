/* Poseidon2 over BabyBear, width 16, S-box x^7, 4 + 13 + 4 rounds: the
 * host permutation of ethrex_tpu/ops/poseidon2.py, round for round.
 *
 * The constants are not defined here: ops/poseidon2.py derives them once
 * (_generate_constants) and hands them to p2_init before the first call.
 * Every value stays canonical (< p) between rounds, so the rows are the
 * ones models/poseidon2_air._generate_trace_py writes in Python ints.
 *
 * Exposed via a tiny C ABI for ctypes:
 *   void p2_init(const uint32_t ext_rc[8*16], const uint32_t int_rc[13],
 *                const uint32_t diag_mu[16]);
 *   void p2_trace(const uint32_t in[16], uint32_t rows[32*16]);
 *       row 0 = M_E(in), row r+1 = round r of row r (r = 0..20),
 *       row 21 = P(in), rows 22..31 = copies of row 21
 */

#include <stdint.h>

#define P 2013265921ULL /* 15 * 2^27 + 1 */
#define WIDTH 16
#define HALF_F 4
#define ROUNDS_F 8
#define ROUNDS_P 13
#define ROWS 32

static uint64_t ext_rc[ROUNDS_F][WIDTH];
static uint64_t int_rc[ROUNDS_P];
static uint64_t diag_mu[WIDTH];

void p2_init(const uint32_t *ext, const uint32_t *intr, const uint32_t *mu) {
    for (int r = 0; r < ROUNDS_F; r++)
        for (int i = 0; i < WIDTH; i++)
            ext_rc[r][i] = ext[r * WIDTH + i] % P;
    for (int r = 0; r < ROUNDS_P; r++)
        int_rc[r] = intr[r] % P;
    for (int i = 0; i < WIDTH; i++)
        diag_mu[i] = mu[i] % P;
}

static inline uint64_t sbox(uint64_t x) {
    uint64_t x2 = x * x % P;
    uint64_t x4 = x2 * x2 % P;
    return x4 * x2 % P * x % P;
}

/* M_E = circ(2*M4, M4, M4, M4) through the 8-addition M4 chain.  Inputs
 * are canonical (< 2^31): no intermediate passes 2^39, so one reduction
 * at the end gives what the Python chain gives reducing at every step. */
static void external_linear(uint64_t s[WIDTH]) {
    uint64_t b[WIDTH];
    for (int k = 0; k < WIDTH; k += 4) {
        uint64_t t0 = s[k] + s[k + 1];
        uint64_t t1 = s[k + 2] + s[k + 3];
        uint64_t t2 = 2 * s[k + 1] + t1;
        uint64_t t3 = 2 * s[k + 3] + t0;
        uint64_t t4 = 4 * t1 + t3;
        uint64_t t5 = 4 * t0 + t2;
        b[k] = t3 + t5;
        b[k + 1] = t5;
        b[k + 2] = t2 + t4;
        b[k + 3] = t4;
    }
    for (int j = 0; j < 4; j++) {
        uint64_t sum = b[j] + b[4 + j] + b[8 + j] + b[12 + j];
        for (int k = 0; k < WIDTH; k += 4)
            s[k + j] = (b[k + j] + sum) % P;
    }
}

static void external_round(uint64_t s[WIDTH], int r) {
    for (int i = 0; i < WIDTH; i++)
        s[i] = sbox((s[i] + ext_rc[r][i]) % P);
    external_linear(s);
}

static void internal_round(uint64_t s[WIDTH], int r) {
    s[0] = sbox((s[0] + int_rc[r]) % P);
    uint64_t tot = 0;
    for (int i = 0; i < WIDTH; i++)
        tot += s[i];
    tot %= P;
    for (int i = 0; i < WIDTH; i++)
        s[i] = (tot + diag_mu[i] * s[i]) % P;
}

static void store(uint32_t *row, const uint64_t s[WIDTH]) {
    for (int i = 0; i < WIDTH; i++)
        row[i] = (uint32_t)s[i];
}

void p2_trace(const uint32_t *in, uint32_t *rows) {
    uint64_t s[WIDTH];
    for (int i = 0; i < WIDTH; i++)
        s[i] = in[i] % P;
    external_linear(s);
    int row = 0;
    store(rows, s);
    for (int r = 0; r < HALF_F; r++) {
        external_round(s, r);
        store(rows + ++row * WIDTH, s);
    }
    for (int r = 0; r < ROUNDS_P; r++) {
        internal_round(s, r);
        store(rows + ++row * WIDTH, s);
    }
    for (int r = HALF_F; r < ROUNDS_F; r++) {
        external_round(s, r);
        store(rows + ++row * WIDTH, s);
    }
    while (++row < ROWS)
        store(rows + row * WIDTH, s);
}
