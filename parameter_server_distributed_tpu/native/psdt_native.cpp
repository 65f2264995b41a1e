// Native host-side kernels for the parameter-server control plane.
//
// The reference implements its entire PS runtime in C++ — in particular the
// aggregation hot loop (sum over workers x tensors x elements, then the SGD
// apply; reference: src/parameter_server.cpp:40-91).  In this framework the
// *device* data plane is XLA-compiled, but the host-side PS (async mode,
// RPC-fed) still sums worker gradients and applies updates on the CPU.
// These kernels do that GIL-free (callers release the GIL via ctypes), with
// a fused single pass per tensor instead of numpy temporaries per operand.
// Production callers: core/optimizer.py (SGD/Momentum/Adam host optimizers)
// and core/ps_core.py (fused barrier mean+SGD apply).
//
// Build: native/__init__.py (g++ -O3 -shared), loaded via ctypes with a
// pure Python/numpy fallback when no compiler is available.  Disable with
// PSDT_NATIVE=0 (the bench A/B knob).

#include <pthread.h>
#if defined(__SSE2__)
#include <emmintrin.h>
#endif

#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <algorithm>
#include <condition_variable>
#include <deque>
#include <mutex>
#include <system_error>
#include <thread>
#include <vector>

// ---------------------------------------------------------------------------
// Wire-codec helpers (ISSUE 6).  The packed tensor payloads of the data
// plane (rpc/codec.py) are byte-layouts pinned by the Python reference
// implementation; every kernel below must reproduce numpy/ml_dtypes
// BIT-FOR-BIT (fuzz-checked in tests/test_codec.py) — the native path is a
// pure speed substitution, never a semantic one.
//
// Destination buffers are raw uint8_t* because protobuf payloads start at
// arbitrary (varint-sized) offsets inside the outgoing message buffer;
// all multi-byte stores go through memcpy, which g++ folds into plain
// unaligned moves.

namespace {

inline void store16(uint8_t* p, uint16_t v) { std::memcpy(p, &v, 2); }
inline void store32(uint8_t* p, uint32_t v) { std::memcpy(p, &v, 4); }
inline uint16_t load16(const uint8_t* p) { uint16_t v; std::memcpy(&v, p, 2); return v; }
inline uint32_t load32(const uint8_t* p) { uint32_t v; std::memcpy(&v, p, 4); return v; }

// f32 -> bf16, round-to-nearest-even with NaN quietization — exactly the
// Eigen/ml_dtypes conversion numpy's astype(bfloat16) performs (verified
// against specials: inf, -0.0, denormals, NaN payloads).  Branchless so
// the pack loop vectorizes (the NaN case becomes a blend, not a branch).
inline uint16_t f32_to_bf16(float f) {
    uint32_t u;
    std::memcpy(&u, &f, 4);
    const uint32_t lsb = (u >> 16) & 1u;
    const uint16_t rne = static_cast<uint16_t>((u + 0x7fffu + lsb) >> 16);
    const uint16_t nan = static_cast<uint16_t>((u >> 16) | 0x0040u);
    return (u & 0x7fffffffu) > 0x7f800000u ? nan : rne;
}

inline float bf16_to_f32(uint16_t h) {
    const uint32_t u = static_cast<uint32_t>(h) << 16;
    float f;
    std::memcpy(&f, &u, 4);
    return f;
}

inline uint32_t abs_bits(float f) {
    uint32_t u;
    std::memcpy(&u, &f, 4);
    return u & 0x7fffffffu;
}

// Exact r-th smallest (0-based) |src| value via a two-round radix select
// over the bit patterns (monotone for non-negative floats).  Round 1 bins
// the TOP 16 bits in one pass (64k bins — sign is zero, so exponent
// clustering in real gradients still splits on high mantissa bits);
// round 2 resolves the low 16 bits over the (tiny) surviving candidate
// set.  Four interleaved partial histograms break the store-forwarding
// dependency chain of the classic single-array histogram loop.
float radix_kth_abs(const float* src, const int64_t n, int64_t r) {
    std::vector<int64_t> hist(4 * 65536, 0);
    int64_t* h0 = hist.data();
    int64_t* h1 = h0 + 65536;
    int64_t* h2 = h1 + 65536;
    int64_t* h3 = h2 + 65536;
    int64_t i = 0;
    for (; i + 4 <= n; i += 4) {
        ++h0[abs_bits(src[i]) >> 16];
        ++h1[abs_bits(src[i + 1]) >> 16];
        ++h2[abs_bits(src[i + 2]) >> 16];
        ++h3[abs_bits(src[i + 3]) >> 16];
    }
    for (; i < n; ++i) ++h0[abs_bits(src[i]) >> 16];
    uint32_t hi = 0;
    int64_t acc = 0;
    for (;; ++hi) {
        const int64_t c = h0[hi] + h1[hi] + h2[hi] + h3[hi];
        if (acc + c > r) break;
        acc += c;
    }
    r -= acc;
    // round 2: low 16 bits of the elements whose top half == hi
    std::vector<uint32_t> low(65536, 0);
    for (int64_t j = 0; j < n; ++j) {
        const uint32_t u = abs_bits(src[j]);
        low[u & 0xffffu] += (u >> 16) == hi;
    }
    uint32_t lo = 0;
    for (acc = 0;; ++lo) {
        if (acc + low[lo] > r) break;
        acc += low[lo];
    }
    const uint32_t bits = (hi << 16) | lo;
    float out;
    std::memcpy(&out, &bits, 4);
    return out;
}


// ---------------------------------------------------------------------------
// The ring's wide move (rpc/shm_transport.py ShmRing._move).  A span of a
// byte ring, wrap and all, is cut into pieces and moved by the caller and
// a few helper threads the library keeps: fork-join inside ONE call, so
// everything the caller holds for the call (a mapping, a cursor not yet
// stored) holds for every piece.

inline void cpu_relax() {
#if defined(__x86_64__) || defined(__i386__)
    __builtin_ia32_pause();
#elif defined(__aarch64__)
    asm volatile("yield");
#endif
}

// memcpy whose stores go past the cache (the destination is read once,
// later, by someone else: it need not push what the caller has there
// out, nor be read for ownership first); plain memcpy where the ISA has
// no such store.
void stream_copy(uint8_t* dst, const uint8_t* src, const int64_t n) {
#if defined(__SSE2__)
    // up to the destination's first 16-byte boundary, then a cache line
    // (four stores) a turn
    int64_t i = std::min<int64_t>(
        n, (16 - (reinterpret_cast<uintptr_t>(dst) & 15)) & 15);
    std::memcpy(dst, src, static_cast<size_t>(i));
    for (; i + 64 <= n; i += 64) {
        const auto* from = reinterpret_cast<const __m128i*>(src + i);
        auto* to = reinterpret_cast<__m128i*>(dst + i);
        const __m128i a = _mm_loadu_si128(from);
        const __m128i b = _mm_loadu_si128(from + 1);
        const __m128i c = _mm_loadu_si128(from + 2);
        const __m128i d = _mm_loadu_si128(from + 3);
        _mm_stream_si128(to, a);
        _mm_stream_si128(to + 1, b);
        _mm_stream_si128(to + 2, c);
        _mm_stream_si128(to + 3, d);
    }
    _mm_sfence();
    std::memcpy(dst + i, src + i, static_cast<size_t>(n - i));
#else
    std::memcpy(dst, src, static_cast<size_t>(n));
#endif
}

void plain_copy(uint8_t* dst, const uint8_t* src, const int64_t n) {
    std::memcpy(dst, src, static_cast<size_t>(n));
}

struct RingSpan {
    uint8_t* ring;     // first payload byte of the ring
    int64_t cap;       // the ring's capacity
    int64_t pos;       // where the span starts in the ring, < cap
    uint8_t* mem;      // the caller's memory, from the span's first byte
    bool into_ring;    // mem -> ring, else ring -> mem
    bool stream;       // with stores past the cache
};

// bytes [a, b) of the span; the wrap may fall inside
void move_piece(const RingSpan& s, const int64_t a, const int64_t b) {
    const int64_t at = (s.pos + a) % s.cap;
    const int64_t n = b - a;
    const int64_t first = std::min(n, s.cap - at);
    const auto copy = s.stream ? stream_copy : plain_copy;
    if (s.into_ring) {
        copy(s.ring + at, s.mem + a, first);
        copy(s.ring, s.mem + a + first, n - first);
    } else {
        copy(s.mem + a, s.ring + at, first);
        copy(s.mem + a + first, s.ring, n - first);
    }
}

struct PieceTask {
    const RingSpan* span;
    int64_t a, b;
    std::atomic<int>* left;   // the call's pieces still out
};

// Both ring ends of one process share it, so a call queues its pieces
// and whoever is free takes them: a helper, or the caller itself once
// its own piece is done (a pool that could start no thread, or lost its
// threads to a fork, still finishes every call).  Spans follow each
// other within microseconds while a frame moves, so a helper out of work
// looks at `queued` for a little while before it sleeps on `work`, and a
// caller at `left` before it sleeps on `done`: a futex wake-up costs a
// good part of a piece's copy.  Never destroyed: the helpers sleep until
// the process exits.
struct Pool {
    std::mutex mu;
    std::condition_variable work;   // a piece was queued
    std::condition_variable done;   // a call's last piece came in
    std::deque<PieceTask> queue;
    std::atomic<int> queued{0};     // queue.size(), readable without mu
    int threads = 0;
    int idle = 0;                   // helpers asleep on `work`
};
constexpr int kMaxHelpers = 16;
constexpr auto kLook = std::chrono::microseconds(100);

// look until `found()` or for kLook, whichever comes first
template <class Found>
bool look_for(Found found) {
    const auto until = std::chrono::steady_clock::now() + kLook;
    for (;;) {
        for (int i = 0; i < 64; ++i) {
            if (found()) return true;
            cpu_relax();
        }
        if (std::chrono::steady_clock::now() >= until) return found();
    }
}

Pool* g_pool = nullptr;
std::once_flag g_pool_once;

// the first queued piece, moved with the lock let go; false: none queued
bool take_one(Pool& pool, std::unique_lock<std::mutex>& lock) {
    if (pool.queue.empty()) return false;
    const PieceTask task = pool.queue.front();
    pool.queue.pop_front();
    pool.queued.fetch_sub(1, std::memory_order_relaxed);
    lock.unlock();
    move_piece(*task.span, task.a, task.b);
    // the caller may return, and `left` die, the moment this reads 0
    const bool last = task.left->fetch_sub(1, std::memory_order_acq_rel) == 1;
    lock.lock();
    if (last) pool.done.notify_all();
    return true;
}

void helper_loop(Pool* pool) {
    std::unique_lock<std::mutex> lock(pool->mu);
    for (;;) {
        if (take_one(*pool, lock)) continue;
        lock.unlock();
        look_for([pool] {
            return pool->queued.load(std::memory_order_relaxed) > 0; });
        lock.lock();
        if (pool->queue.empty()) {
            ++pool->idle;
            pool->work.wait(lock);
            --pool->idle;
        }
    }
}

Pool* the_pool() {
    std::call_once(g_pool_once, [] {
        g_pool = new Pool;
        // a forked child has the parent's pool and none of its threads
        // (and perhaps its mutex, held): it starts over with its own
        pthread_atfork(nullptr, nullptr, [] { g_pool = new Pool; });
    });
    return g_pool;
}

}  // namespace

extern "C" {

// out[i] = sum_w srcs[w][i] / count   (the barrier mean,
// mean-over-actual-contributors semantics)
void psdt_mean(const float** srcs, int32_t count, const int64_t n,
               float* out) {
    if (count <= 0) return;
    const float inv = 1.0f / static_cast<float>(count);
    // first source initializes, remaining accumulate, single store pass
    for (int64_t i = 0; i < n; ++i) {
        float acc = srcs[0][i];
        for (int32_t w = 1; w < count; ++w) acc += srcs[w][i];
        out[i] = acc * inv;
    }
}

// The host optimizers' sweeps (core/optimizer.py).  Each reads the old
// parameters and writes the new ones to a SEPARATE output, so the caller
// never copies the store before the sweep; the barrier close hands each
// call an element range of a tensor (core/ps_core.py), and every kernel
// is elementwise, so any cut gives the whole tensor's result bit for bit.
// ``out`` overlaps no input (__restrict__: the loops vectorize without
// run-time overlap checks); optimizer slots update in place.

// out = param - lr * grad   (the reference's update rule at lr=1.0)
void psdt_sgd_out(const float* __restrict__ param,
                  const float* __restrict__ grad, float* __restrict__ out,
                  const int64_t n, const float lr) {
    for (int64_t i = 0; i < n; ++i) out[i] = param[i] - lr * grad[i];
}

// velocity = mu * velocity + grad; out = param - lr * velocity  (one pass)
void psdt_momentum_out(const float* __restrict__ param,
                       const float* __restrict__ grad,
                       float* __restrict__ velocity, float* __restrict__ out,
                       const int64_t n, const float lr, const float mu) {
    for (int64_t i = 0; i < n; ++i) {
        const float v = mu * velocity[i] + grad[i];
        velocity[i] = v;
        out[i] = param[i] - lr * v;
    }
}

// Adam fused pass.  bc1/bc2 are the bias-correction denominators.
void psdt_adam_out(const float* __restrict__ param,
                   const float* __restrict__ grad, float* __restrict__ m,
                   float* __restrict__ v, float* __restrict__ out,
                   const int64_t n, const float lr, const float b1,
                   const float b2, const float eps, const float bc1,
                   const float bc2) {
    for (int64_t i = 0; i < n; ++i) {
        const float g = grad[i];
        const float m_new = b1 * m[i] + (1.0f - b1) * g;
        const float v_new = b2 * v[i] + (1.0f - b2) * g * g;
        m[i] = m_new;
        v[i] = v_new;
        const float m_hat = m_new / bc1;
        const float v_hat = v_new / bc2;
        out[i] = param[i] - lr * m_hat / (__builtin_sqrtf(v_hat) + eps);
    }
}

// AdamW fused pass: Adam plus decoupled weight decay folded into the SAME
// sweep (optax.adamw convention: update = adam_term + wd * p_pre, applied
// together from the pre-update param).  wd = 0 for non-decayed tensors
// (the matrices-only mask lives in the Python caller).
void psdt_adamw_out(const float* __restrict__ param,
                    const float* __restrict__ grad, float* __restrict__ m,
                    float* __restrict__ v, float* __restrict__ out,
                    const int64_t n, const float lr, const float b1,
                    const float b2, const float eps, const float bc1,
                    const float bc2, const float wd) {
    for (int64_t i = 0; i < n; ++i) {
        const float g = grad[i];
        const float p_old = param[i];
        const float m_new = b1 * m[i] + (1.0f - b1) * g;
        const float v_new = b2 * v[i] + (1.0f - b2) * g * g;
        m[i] = m_new;
        v[i] = v_new;
        const float m_hat = m_new / bc1;
        const float v_hat = v_new / bc2;
        out[i] = p_old
            - lr * (m_hat / (__builtin_sqrtf(v_hat) + eps) + wd * p_old);
    }
}

// Fused mean + SGD: param -= lr * mean(srcs) with no intermediate buffer.
void psdt_mean_sgd(float* param, const float** srcs, int32_t count,
                   const int64_t n, const float lr) {
    if (count <= 0) return;
    const float scale = lr / static_cast<float>(count);
    for (int64_t i = 0; i < n; ++i) {
        float acc = srcs[0][i];
        for (int32_t w = 1; w < count; ++w) acc += srcs[w][i];
        param[i] -= scale * acc;
    }
}

// One span between the caller's memory and a byte ring, cut over `width`
// threads (the caller is one of them); returns when every byte has moved.
// `pos + n` may pass `cap`: the span wraps.  `flags`: 1 the span goes INTO
// the ring (else out of it), 2 with stores past the cache.  Pieces are
// cut on 4 KB boundaries of the span; width 1 is a memcpy on the caller's
// thread.
// Exported so that the shm transport's rings (rpc/shm_transport.py) move
// their bytes WITHOUT the GIL: ctypes releases it around the call, so a
// colocated producer/consumer pair really overlaps its copies, where
// memoryview slice assignment would convoy them a GIL switch-interval at
// a time.
void psdt_ring_move(uint8_t* ring, const int64_t cap, const int64_t pos,
                    uint8_t* mem, const int64_t n, const int32_t flags,
                    const int32_t width) {
    const RingSpan span{ring, cap, pos % cap, mem, (flags & 1) != 0,
                        (flags & 2) != 0};
    const int64_t pieces = std::max<int64_t>(
        1, std::min<int64_t>(width, n >> 12));
    if (pieces == 1) {
        move_piece(span, 0, n);
        return;
    }
    const int64_t step = ((n + pieces - 1) / pieces + 4095) & ~int64_t{4095};
    Pool& pool = *the_pool();
    std::atomic<int> left{0};
    std::unique_lock<std::mutex> lock(pool.mu);
    for (int64_t a = step; a < n; a += step) {
        pool.queue.push_back({&span, a, std::min(a + step, n), &left});
        left.fetch_add(1, std::memory_order_relaxed);
    }
    const int queued = static_cast<int>(pool.queue.size());
    pool.queued.store(queued, std::memory_order_relaxed);
    // as many helpers as pieces were ever queued at once; those that are
    // looking find the pieces themselves, sleepers are woken
    while (pool.threads < std::min(queued, kMaxHelpers)) {
        try {
            std::thread(helper_loop, &pool).detach();
            ++pool.threads;
        } catch (const std::system_error&) {
            break;  // no thread to be had: the callers move the pieces
        }
    }
    for (int i = std::min(queued, pool.idle); i > 0; --i)
        pool.work.notify_one();
    lock.unlock();
    move_piece(span, 0, step);
    const auto all_in = [&left] {
        return left.load(std::memory_order_acquire) == 0; };
    lock.lock();
    while (take_one(pool, lock)) {}  // what no helper has come for yet
    lock.unlock();
    if (look_for(all_in)) return;
    lock.lock();
    while (!all_in())
        if (!take_one(pool, lock)) pool.done.wait(lock);
}

// ---------------------------------------------------------------------------
// Wire codec kernels (rpc/codec.py NativeCodec).  Layouts are the Python
// reference's, byte for byte.

// WIRE_BF16 payload: n * u16 (RNE-rounded), little-endian.
void psdt_pack_bf16(const float* src, const int64_t n, uint8_t* dst) {
    for (int64_t i = 0; i < n; ++i) store16(dst + 2 * i, f32_to_bf16(src[i]));
}

void psdt_unpack_bf16(const uint8_t* src, const int64_t n, float* dst) {
    for (int64_t i = 0; i < n; ++i) dst[i] = bf16_to_f32(load16(src + 2 * i));
}

// WIRE_INT8 payload: f32 max-abs scale | n * int8.  Scale and quantization
// mirror the numpy path exactly: max|src| reduced in f32, scale computed in
// DOUBLE (max_abs / 127.0 — Python float arithmetic) then narrowed to f32,
// division + round-half-even in f32 (numpy casts the scalar to the array
// dtype; np.rint == roundeven, which unlike rintf has no FP-environment
// side effects and therefore vectorizes), clip to [-127, 127].
void psdt_quant_int8(const float* src, const int64_t n, uint8_t* dst) {
    // max|src| as an INTEGER max over the abs bit patterns (monotone for
    // non-negative floats, and integer MAX_EXPR vectorizes without any
    // fast-math relaxation) — exact, association-free
    uint32_t mx = 0;
    for (int64_t i = 0; i < n; ++i) {
        uint32_t u;
        std::memcpy(&u, src + i, 4);
        u &= 0x7fffffffu;
        mx = u > mx ? u : mx;
    }
    float max_abs;
    std::memcpy(&max_abs, &mx, 4);
    const float scale = max_abs > 0.0f
        ? static_cast<float>(static_cast<double>(max_abs) / 127.0) : 1.0f;
    std::memcpy(dst, &scale, 4);
    int8_t* q = reinterpret_cast<int8_t*>(dst + 4);
    // round-half-even via the 1.5*2^23 magic-add trick: EXACT for every
    // reachable quotient (|src/scale| <= 127 by construction of scale),
    // and plain add/sub — unlike rintf/roundevenf it vectorizes.  The
    // only divergence from np.rint is -0.0 vs +0.0, erased by the int8
    // cast.  Byte-identity with the numpy oracle is fuzz-pinned
    // (tests/test_codec.py).
    const float magic = 12582912.0f;
    for (int64_t j = 0; j < n; ++j) {
        float r = (src[j] / scale + magic) - magic;
        r = r < -127.0f ? -127.0f : (r > 127.0f ? 127.0f : r);
        q[j] = static_cast<int8_t>(r);
    }
}

// payload -> f32: q * scale, both factors f32 (numpy: int8.astype(f32) * f32).
void psdt_dequant_int8(const uint8_t* src, const int64_t n, float* dst) {
    float scale;
    std::memcpy(&scale, src, 4);
    const int8_t* q = reinterpret_cast<const int8_t*>(src + 4);
    for (int64_t i = 0; i < n; ++i)
        dst[i] = static_cast<float>(q[i]) * scale;
}

// WIRE_TOPK payload: u32 k | k * u32 indices (ascending) | k * bf16 values.
// Deterministic selection shared with the Python oracle (rpc/codec.py
// topk_indices): take every element with |v| strictly above the k-th
// largest |v|, then fill the remaining slots with threshold-tied elements
// in ASCENDING INDEX order — tie-breaking is part of the codec contract so
// native and Python emit identical bytes.
void psdt_topk_pack(const float* src, const int64_t n, const int64_t k,
                    uint8_t* dst) {
    store32(dst, static_cast<uint32_t>(k));
    if (k <= 0) return;
    uint8_t* idst = dst + 4;
    uint8_t* vdst = dst + 4 + 4 * k;
    if (k >= n) {
        for (int64_t i = 0; i < n; ++i) {
            store32(idst + 4 * i, static_cast<uint32_t>(i));
            store16(vdst + 2 * i, f32_to_bf16(src[i]));
        }
        return;
    }
    const float thr = radix_kth_abs(src, n, n - k);
    int64_t above = 0;
    for (int64_t i = 0; i < n; ++i) above += std::fabs(src[i]) > thr;
    int64_t need = k - above;
    int64_t taken = 0;
    for (int64_t i = 0; i < n && taken < k; ++i) {
        const float a = std::fabs(src[i]);
        if (a > thr || (a == thr && need > 0)) {
            if (!(a > thr)) --need;
            store32(idst + 4 * taken, static_cast<uint32_t>(i));
            store16(vdst + 2 * taken, f32_to_bf16(src[i]));
            ++taken;
        }
    }
    if (taken < k) {
        // NaN entries compare false against any threshold (and a NaN
        // threshold against anything) but sort as the LARGEST values —
        // fill the remaining slots with the FIRST (k - taken) NaN
        // indices, merged ascending into the selection, exactly like
        // the Python oracle (codec contract: always exactly k entries).
        std::vector<uint32_t> nans;
        nans.reserve(static_cast<size_t>(k - taken));
        for (int64_t i = 0; i < n
                 && static_cast<int64_t>(nans.size()) < k - taken; ++i)
            if (src[i] != src[i]) nans.push_back(static_cast<uint32_t>(i));
        int64_t r = taken - 1;                               // read (sel)
        int64_t nw = static_cast<int64_t>(nans.size()) - 1;  // read (nan)
        int64_t w = taken + static_cast<int64_t>(nans.size()) - 1;
        while (nw >= 0) {
            if (r >= 0
                && load32(idst + 4 * r) > nans[static_cast<size_t>(nw)]) {
                store32(idst + 4 * w, load32(idst + 4 * r));
                store16(vdst + 2 * w, load16(vdst + 2 * r));
                --r;
            } else {
                const uint32_t idx = nans[static_cast<size_t>(nw)];
                store32(idst + 4 * w, idx);
                store16(vdst + 2 * w, f32_to_bf16(src[idx]));
                --nw;
            }
            --w;
        }
    }
}

// payload -> dense f32 (zero-filled, kept entries scattered back).  Returns
// 0 on success, -1 when any index is out of range (caller falls back to the
// Python path, which raises) — a silent skip would quietly corrupt decode.
int32_t psdt_topk_unpack(const uint8_t* src, const int64_t total,
                         float* dst) {
    const int64_t k = static_cast<int64_t>(load32(src));
    std::memset(dst, 0, static_cast<size_t>(total) * 4);
    const uint8_t* isrc = src + 4;
    const uint8_t* vsrc = src + 4 + 4 * k;
    for (int64_t j = 0; j < k; ++j) {
        const uint32_t idx = load32(isrc + 4 * j);
        if (static_cast<int64_t>(idx) >= total) return -1;
        dst[idx] = bf16_to_f32(load16(vsrc + 2 * j));
    }
    return 0;
}

}  // extern "C"
