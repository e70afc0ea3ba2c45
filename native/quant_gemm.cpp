// Native int8 GEMM for the quantized serving path.
//
// XLA's CPU backend (jaxlib 0.4.36) has no int8 dot emitter: an s8xs8
// dot_general materializes an s32 copy of the weight operand and runs
// the f32-style loop over it (~0.2x fp32 — see docs/design.md
// "Quantized serving"). This rig's Xeon has AVX512-VNNI, whose
// vpdpbusd does 64 u8xs8 MACs per instruction, so the honest way to an
// int8 serving win on CPU is the same route the repo already takes for
// host ETL: a tiny native library behind ctypes, probed at runtime and
// A/B'd against the XLA path before dispatch ships it.
//
// Contract (quant_matmul callers): out[b,n] = sum_k x[b,k] * w[n,k],
// x s8 [B,K] row-major, w s8 [N,K] row-major (weights stored transposed
// so each output channel is a unit-stride row), out s32 [B,N].
//
// vpdpbusd is unsigned x signed. We bias the WEIGHT operand on the fly
// (w_u8 = w ^ 0x80 == w + 128 in biased u8) and subtract the exact
// correction 128 * rowsum(x[b,:]) afterwards — no extra sidecar data
// and no precision loss (all-integer arithmetic).
//
// ISA safety: the base translation unit compiles with the Makefile's
// -mtune-only flags; the VNNI kernel lives behind a gcc target
// attribute and is only ever called after __builtin_cpu_supports
// checks, so the shared .so cannot SIGILL on an older host (same rule
// as etl.cpp's -mtune note). A portable scalar kernel is the fallback.

#include <cstdint>
#include <immintrin.h>
#ifdef _OPENMP
#include <omp.h>
#endif

// XLA typed-FFI handler (jaxlib ships the header-only API under
// jaxlib/include; the Makefile asks jax.ffi for the path). The serving
// path registers it as a real XLA custom call: XLA hands the kernel raw
// buffer pointers in-process, with no python trampoline or operand
// marshalling in the way. The plain ctypes entry stays for host code
// and tests.

namespace {

#if defined(__x86_64__) && defined(__GNUC__)
#define DL4JTPU_VNNI_BUILT 1

__attribute__((target("avx512f,avx512bw,avx512vl,avx512vnni")))
void gemm_vnni(const int8_t* x, const int8_t* w, int32_t* out,
               int64_t B, int64_t K, int64_t N) {
    const __m512i bias = _mm512_set1_epi8(static_cast<char>(0x80));
    const int64_t ktail = K % 64;
    const __mmask64 tmask =
        ktail ? ((~__mmask64{0}) >> (64 - ktail)) : 0;
    // Block over batch rows so each streamed weight vector feeds up to
    // 8 accumulators: w (the big operand) crosses memory ceil(B/8)
    // times while x (tiny, L2-resident) is re-read per channel.
    for (int64_t b0 = 0; b0 < B; b0 += 8) {
        const int bb = static_cast<int>(B - b0 < 8 ? B - b0 : 8);
        int32_t corr[8];
        for (int j = 0; j < bb; ++j) {
            const int8_t* xr = x + (b0 + j) * K;
            int32_t s = 0;
            for (int64_t k = 0; k < K; ++k) s += xr[k];
            corr[j] = 128 * s;
        }
#pragma omp parallel for schedule(static) if (N * K > (int64_t{1} << 18))
        for (int64_t n = 0; n < N; ++n) {
            const int8_t* wr = w + n * K;
            __m512i acc[8];
            for (int j = 0; j < bb; ++j) acc[j] = _mm512_setzero_si512();
            int64_t k = 0;
            for (; k + 64 <= K; k += 64) {
                const __m512i wu = _mm512_xor_si512(
                    _mm512_loadu_si512(wr + k), bias);
                for (int j = 0; j < bb; ++j) {
                    const __m512i xv = _mm512_loadu_si512(
                        x + (b0 + j) * K + k);
                    acc[j] = _mm512_dpbusd_epi32(acc[j], wu, xv);
                }
            }
            if (ktail) {
                const __m512i wu = _mm512_xor_si512(
                    _mm512_maskz_loadu_epi8(tmask, wr + k), bias);
                for (int j = 0; j < bb; ++j) {
                    const __m512i xv = _mm512_maskz_loadu_epi8(
                        tmask, x + (b0 + j) * K + k);
                    acc[j] = _mm512_dpbusd_epi32(acc[j], wu, xv);
                }
            }
            for (int j = 0; j < bb; ++j) {
                out[(b0 + j) * N + n] =
                    _mm512_reduce_add_epi32(acc[j]) - corr[j];
            }
        }
    }
}
#endif  // __x86_64__ && __GNUC__

void gemm_scalar(const int8_t* x, const int8_t* w, int32_t* out,
                 int64_t B, int64_t K, int64_t N) {
#pragma omp parallel for schedule(static) \
    if (B * N * K > (int64_t{1} << 18))
    for (int64_t b = 0; b < B; ++b) {
        const int8_t* xr = x + b * K;
        for (int64_t n = 0; n < N; ++n) {
            const int8_t* wr = w + n * K;
            int32_t s = 0;
            for (int64_t k = 0; k < K; ++k) {
                s += static_cast<int32_t>(xr[k])
                     * static_cast<int32_t>(wr[k]);
            }
            out[b * N + n] = s;
        }
    }
}

}  // namespace

extern "C" {

// Bump on any signature change; the ctypes loader rebuilds once on
// mismatch (same protocol as etl_abi_version). v2: XLA FFI handler;
// v3: the handler is unconditional (no build without it).
int32_t quant_abi_version() { return 3; }

// 1 when the AVX512-VNNI kernel is compiled in AND the running CPU
// supports it; the Python probe reports which path a measurement used.
int32_t int8_gemm_vnni_available() {
#ifdef DL4JTPU_VNNI_BUILT
    return __builtin_cpu_supports("avx512f")
           && __builtin_cpu_supports("avx512bw")
           && __builtin_cpu_supports("avx512vl")
           && __builtin_cpu_supports("avx512vnni") ? 1 : 0;
#else
    return 0;
#endif
}

// out[b,n] = sum_k x[b,k] * w[n,k]; picks VNNI when the CPU has it.
void int8_gemm(const int8_t* x, const int8_t* w, int32_t* out,
               int64_t B, int64_t K, int64_t N) {
#ifdef DL4JTPU_VNNI_BUILT
    if (int8_gemm_vnni_available()) {
        gemm_vnni(x, w, out, B, K, N);
        return;
    }
#endif
    gemm_scalar(x, w, out, B, K, N);
}

}  // extern "C"

#include "xla/ffi/api/ffi.h"

namespace ffi = xla::ffi;

static ffi::Error Int8GemmFfiImpl(ffi::Buffer<ffi::S8> x,
                                  ffi::Buffer<ffi::S8> w,
                                  ffi::ResultBuffer<ffi::S32> out) {
    const auto xd = x.dimensions();
    const auto wd = w.dimensions();
    if (xd.size() != 2 || wd.size() != 2 || xd[1] != wd[1]) {
        return ffi::Error::InvalidArgument(
            "int8_gemm wants x[B,K] and w[N,K] (weights transposed)");
    }
    int8_gemm(x.typed_data(), w.typed_data(), out->typed_data(),
              xd[0], xd[1], wd[0]);
    return ffi::Error::Success();
}

// Exported handler symbol; native_quant.py wraps it in a PyCapsule and
// registers it as the "dl4jtpu_int8_gemm" custom-call target.
XLA_FFI_DEFINE_HANDLER_SYMBOL(
    dl4jtpu_int8_gemm_ffi, Int8GemmFfiImpl,
    ffi::Ffi::Bind()
        .Arg<ffi::Buffer<ffi::S8>>()
        .Arg<ffi::Buffer<ffi::S8>>()
        .Ret<ffi::Buffer<ffi::S32>>());
