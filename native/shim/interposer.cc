// libtpushim — PJRT C-API interposer (the libgemhook.so.1 equivalent).
//
// LD_PRELOADed into fractional-TPU containers by the scheduler's env
// injection (ref pkg/scheduler/pod.go:446-449 injected the CUDA hook the
// same way).  Where Gemini intercepted CUDA driver calls before each kernel
// launch, XLA launches whole compiled programs, so the interception point is
// PJRT_LoadedExecutable_Execute: acquire a time-quota token from the pod
// broker, run the execution, report measured *device* time (SURVEY §7.2).
//
// Two hook paths cover how runtimes load libtpu:
//  1. direct linking: our exported GetPjrtApi shadows the real one,
//  2. dlopen+dlsym (JAX, PyTorch/XLA): we interpose dlsym and rewrite
//     lookups of "GetPjrtApi" (Gemini hooked cuGetProcAddress likewise).
//
// Enforcement semantics:
//  * Compute time is charged completion-to-completion: Execute registers an
//    OnReady callback on the execution's device_complete_event and charges
//    ready_time - max(dispatch_start, previous_ready) — the device-occupancy
//    span — not the dispatch wall time, which on async runtimes acks in
//    microseconds regardless of FLOPs.  Falls back to dispatch wall time
//    when the runtime offers no events.
//  * HBM caps are enforced HARD by default: an over-cap upload returns a
//    fabricated RESOURCE_EXHAUSTED PJRT_Error without reaching the real
//    plugin (Gemini rejected over-cap cuMemAlloc the same way).  Set
//    TPUSHARE_MEM_ENFORCE=soft for log-and-account-only.
//  * Every PJRT allocation path in the vendored API is covered — uploads
//    (BufferFromHostBuffer), the async transfer manager, DmaMap,
//    device-to-device copies, executable outputs, and client-init
//    preallocation; aliasing views are accounted explicitly at zero size
//    (Gemini capped every CUDA alloc; SURVEY §7.4 flags client-init
//    preallocation as the TPU-specific hard part):
//      - client-init preallocation: a library constructor exports the
//        XLA allocator-fraction env from TPUSHARE_MEM_FRACTION before the
//        runtime starts, and PJRT_Client_Create injects memory_fraction /
//        preallocate=false create options (retried without them when the
//        plugin rejects them as unknown — INVALID_ARGUMENT/UNIMPLEMENTED;
//        any other create failure is the caller's and propagates unchanged);
//      - executable outputs: after each Execute the output buffers are
//        charged on first sighting (size via Buffer_OnDeviceSizeInBytes).
//        An output the broker denies goes on a local OVERFLOW ledger: the
//        pod is now over cap, so in hard mode every subsequent upload AND
//        execute is denied until enough buffers are destroyed;
//      - device-to-device copies: PJRT_Buffer_CopyToDevice allocates a
//        same-size target buffer, so the copy is charged up front (sized
//        from the source — the only pre-copy observable) and the target
//        rides the per-buffer ledger like an upload;
//      - aliased views: PJRT_Client_CreateViewOfDeviceBuffer wraps memory
//        some OTHER library allocated (dlpack import) — the view is
//        recorded at ZERO size so its destroy can never credit bytes the
//        shim never charged, and an Execute re-sighting can never charge
//        it as fresh HBM.
//  * Accounting is symmetric: only buffers this shim charged are credited
//    back on destroy, by exactly the charged amount — the ledger can
//    never drift toward zero from buffers it never saw.  Client destroy
//    releases every buffer wholesale, so it settles all ledgers and
//    credits the broker for the outstanding charge.
//
// The PJRT_Api table is copied and entry pointers swapped; a struct_size
// check skips hooking when the runtime's API is older than the header we
// compiled against.  Only the first plugin's table is wrapped — a second
// distinct plugin resolved through the same process passes through unhooked
// (fractional pods get exactly one visible TPU plugin).  Python/JAX
// deployments can skip LD_PRELOAD entirely and use the in-process ctypes
// guard (kubeshare_tpu.isolation).

#include <dlfcn.h>

#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <set>
#include <string>
#include <unordered_map>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

extern "C" {
int tpushare_init_from_env(void);
double tpushare_acquire(double est_ms);
int tpushare_release(double used_ms);
int tpushare_mem_request(long long delta_bytes);
}

namespace {

typedef const PJRT_Api* (*GetPjrtApiFn)(void);

PJRT_Error* (*g_real_execute)(PJRT_LoadedExecutable_Execute_Args*) = nullptr;
PJRT_Error* (*g_real_buffer_from_host)(PJRT_Client_BufferFromHostBuffer_Args*) =
    nullptr;
PJRT_Error* (*g_real_buffer_destroy)(PJRT_Buffer_Destroy_Args*) = nullptr;
void (*g_real_error_destroy)(PJRT_Error_Destroy_Args*) = nullptr;
void (*g_real_error_message)(PJRT_Error_Message_Args*) = nullptr;
PJRT_Error* (*g_real_error_get_code)(PJRT_Error_GetCode_Args*) = nullptr;
PJRT_Error* (*g_real_event_on_ready)(PJRT_Event_OnReady_Args*) = nullptr;
PJRT_Error* (*g_real_event_destroy)(PJRT_Event_Destroy_Args*) = nullptr;
PJRT_Error* (*g_real_client_create)(PJRT_Client_Create_Args*) = nullptr;
PJRT_Error* (*g_real_client_destroy)(PJRT_Client_Destroy_Args*) = nullptr;
PJRT_Error* (*g_real_buffer_size)(PJRT_Buffer_OnDeviceSizeInBytes_Args*) =
    nullptr;
PJRT_Error* (*g_real_get_executable)(PJRT_LoadedExecutable_GetExecutable_Args*) =
    nullptr;
PJRT_Error* (*g_real_executable_num_outputs)(PJRT_Executable_NumOutputs_Args*) =
    nullptr;
PJRT_Error* (*g_real_executable_destroy)(PJRT_Executable_Destroy_Args*) =
    nullptr;
PJRT_Error* (*g_real_loaded_destroy)(PJRT_LoadedExecutable_Destroy_Args*) =
    nullptr;

bool g_gated = false;
bool g_mem_soft = false;

void DestroyRealError(PJRT_Error* error) {
  if (error == nullptr || g_real_error_destroy == nullptr) return;
  PJRT_Error_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  args.error = error;
  g_real_error_destroy(&args);
}

// Code of a plugin-owned error, or -1 when it cannot be read.
int RealErrorCode(PJRT_Error* error) {
  if (error == nullptr || g_real_error_get_code == nullptr) return -1;
  PJRT_Error_GetCode_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Error_GetCode_Args_STRUCT_SIZE;
  args.error = error;
  if (PJRT_Error* err = g_real_error_get_code(&args)) {
    DestroyRealError(err);
    return -1;
  }
  return static_cast<int>(args.code);
}

// TPUSHARE_MEM_FRACTION parsed once; <= 0 when absent/invalid.
double MemFraction() {
  static double fraction = [] {
    const char* raw = std::getenv("TPUSHARE_MEM_FRACTION");
    if (raw == nullptr || *raw == '\0') return -1.0;
    char* end = nullptr;
    double value = std::strtod(raw, &end);
    if (end == raw || value <= 0.0 || value > 1.0) return -1.0;
    return value;
  }();
  return fraction;
}

// ---------------------------------------------------------------------------
// Fabricated errors.  PJRT_Error is plugin-opaque, so we mint our own
// objects and service the three error entry points for them, forwarding
// everything else to the real plugin.
// ---------------------------------------------------------------------------

struct ShimError {
  std::string message;
  PJRT_Error_Code code;
};

std::mutex g_error_mu;
std::set<const void*>& ShimErrors() {
  static std::set<const void*>* errors = new std::set<const void*>;
  return *errors;  // leaked: see RetiredEvents
}

PJRT_Error* MakeShimError(PJRT_Error_Code code, std::string message) {
  auto* error = new ShimError{std::move(message), code};
  std::lock_guard<std::mutex> lock(g_error_mu);
  ShimErrors().insert(error);
  return reinterpret_cast<PJRT_Error*>(error);
}

ShimError* AsShimError(const PJRT_Error* error) {
  std::lock_guard<std::mutex> lock(g_error_mu);
  if (ShimErrors().count(error) == 0) return nullptr;
  return reinterpret_cast<ShimError*>(const_cast<PJRT_Error*>(error));
}

void HookedErrorDestroy(PJRT_Error_Destroy_Args* args) {
  if (args->error != nullptr) {
    std::lock_guard<std::mutex> lock(g_error_mu);
    auto it = ShimErrors().find(args->error);
    if (it != ShimErrors().end()) {
      ShimErrors().erase(it);
      delete reinterpret_cast<ShimError*>(args->error);
      return;
    }
  }
  if (g_real_error_destroy != nullptr) g_real_error_destroy(args);
}

void HookedErrorMessage(PJRT_Error_Message_Args* args) {
  if (ShimError* shim = AsShimError(args->error)) {
    args->message = shim->message.c_str();
    args->message_size = shim->message.size();
    return;
  }
  if (g_real_error_message != nullptr) g_real_error_message(args);
}

PJRT_Error* HookedErrorGetCode(PJRT_Error_GetCode_Args* args) {
  if (ShimError* shim = AsShimError(args->error)) {
    args->code = shim->code;
    return nullptr;
  }
  if (g_real_error_get_code != nullptr) return g_real_error_get_code(args);
  return nullptr;
}

// ---------------------------------------------------------------------------
// HBM accounting: charge host->device uploads against the pod's cap via the
// broker's MEM protocol; credit exactly the charged amount on destroy.
// ---------------------------------------------------------------------------

std::mutex g_mem_mu;
std::unordered_map<const void*, long long>& ChargedBuffers() {
  static auto* charged = new std::unordered_map<const void*, long long>;
  return *charged;  // leaked: see RetiredEvents
}

// Output buffers the broker DENIED: the pod is over cap by this much.
// The broker ledger stays at <= cap; the shim carries the excess locally
// and (in hard mode) refuses further uploads/executes until destroys
// bring the overflow back to zero.
long long g_overflow_bytes = 0;  // guarded by g_mem_mu
std::unordered_map<const void*, long long>& OverflowBuffers() {
  static auto* overflow = new std::unordered_map<const void*, long long>;
  return *overflow;  // leaked: see RetiredEvents
}

long long OverflowBytes() {
  std::lock_guard<std::mutex> lock(g_mem_mu);
  return g_overflow_bytes;
}

long long ElementBytes(PJRT_Buffer_Type type) {
  switch (type) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 2;
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64:
    case PJRT_Buffer_Type_C64:
      return 8;
    case PJRT_Buffer_Type_C128:
      return 16;
    default:
      return 4;  // S32/U32/F32 and a safe default for exotic types
  }
}

PJRT_Error* HookedBufferFromHost(PJRT_Client_BufferFromHostBuffer_Args* args) {
  if (!g_gated || args->dims == nullptr) return g_real_buffer_from_host(args);
  long long elements = 1;
  for (size_t i = 0; i < args->num_dims; i++) elements *= args->dims[i];
  long long bytes = elements * ElementBytes(args->type);
  long long overflow = OverflowBytes();
  if (overflow > 0 && !g_mem_soft) {
    // executable outputs already hold the pod over its cap: no new
    // uploads until destroys clear the overflow
    char msg[200];
    std::snprintf(msg, sizeof(msg),
                  "tpushare: HBM cap exceeded: pod is %lld bytes over its "
                  "gpu_mem cap (executable outputs); %lld-byte upload denied",
                  overflow, bytes);
    std::fprintf(stderr, "tpushim: %s\n", msg);
    return MakeShimError(PJRT_Error_Code_RESOURCE_EXHAUSTED, msg);
  }
  int rc = tpushare_mem_request(bytes);
  bool charged = rc > 0;
  if (rc == 0) {  // broker said DENY; rc<0 (broker gone) fails open
    if (!g_mem_soft) {
      char msg[160];
      std::snprintf(msg, sizeof(msg),
                    "tpushare: HBM cap exceeded: %lld-byte host-to-device "
                    "upload denied (pod over its gpu_mem cap)",
                    bytes);
      std::fprintf(stderr, "tpushim: %s\n", msg);
      return MakeShimError(PJRT_Error_Code_RESOURCE_EXHAUSTED, msg);
    }
    std::fprintf(stderr,
                 "tpushim: HBM cap exceeded by %lld-byte upload "
                 "(soft mode; not denied)\n", bytes);
  }
  PJRT_Error* err = g_real_buffer_from_host(args);
  if (err == nullptr && charged && args->buffer != nullptr) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    ChargedBuffers()[args->buffer] += bytes;
  } else if (err != nullptr && charged) {
    tpushare_mem_request(-bytes);  // upload failed: roll the charge back
  }
  return err;
}

PJRT_Error* HookedBufferDestroy(PJRT_Buffer_Destroy_Args* args) {
  if (g_gated && args->buffer != nullptr) {
    long long credit = 0;
    {
      std::lock_guard<std::mutex> lock(g_mem_mu);
      auto it = ChargedBuffers().find(args->buffer);
      if (it != ChargedBuffers().end()) {
        credit = it->second;
        ChargedBuffers().erase(it);
      }
      auto over = OverflowBuffers().find(args->buffer);
      if (over != OverflowBuffers().end()) {
        // broker never recorded this charge: clear it locally, no credit
        g_overflow_bytes -= over->second;
        if (g_overflow_bytes < 0) g_overflow_bytes = 0;
        OverflowBuffers().erase(over);
      }
    }
    // credit only what we charged: buffers we never saw (device-to-device
    // copies, a second plugin's buffers) must not drift usage toward zero
    if (credit > 0) tpushare_mem_request(-credit);
  }
  return g_real_buffer_destroy(args);
}

// -----------------------------------------------------------------------
// Async host-to-device transfer-manager accounting (VERDICT r4 #2): newer
// JAX device_put paths allocate through
// PJRT_Client_CreateBuffersForAsyncHostToDevice + TransferData instead of
// BufferFromHostBuffer — without these hooks a pod uploads unmetered.
// Allocation happens at CREATE (the manager pre-allocates every requested
// shape before any TransferData), so the full byte size of all shapes is
// charged there and an over-cap create is denied like an upload.
// RetrieveBuffer moves each buffer's share of the charge onto the regular
// per-buffer ledger so Buffer_Destroy credits it; TransferManager_Destroy
// credits whatever was never retrieved.  Charge/credit stays symmetric:
// only bytes this shim charged are ever credited.
// -----------------------------------------------------------------------

struct TransferManagerCharge {
  std::vector<long long> per_buffer;  // -1 once retrieved
  long long remaining = 0;            // sum of unretrieved entries
};
std::unordered_map<const void*, TransferManagerCharge>& TransferManagers() {
  static auto* tms =
      new std::unordered_map<const void*, TransferManagerCharge>;
  return *tms;  // guarded by g_mem_mu; leaked: see RetiredEvents
}

// Host regions pinned device-visible via PJRT_Client_DmaMap.  Charged
// against the same cap: the mapping is device-addressable staging a pod
// could otherwise route unbounded data through (Gemini's posture was cap
// EVERY alloc, ref pod.go:446-449 chain); soft mode logs instead.
std::unordered_map<const void*, long long>& DmaMapped() {
  static auto* mapped = new std::unordered_map<const void*, long long>;
  return *mapped;  // guarded by g_mem_mu; leaked: see RetiredEvents
}

// Shared deny-or-charge preamble for the upload-shaped paths (upload,
// async create, dma map): returns false when the request must be denied
// (hard mode, over cap); *charged says whether the broker recorded it.
bool ChargeUploadBytes(long long bytes, const char* what, bool* charged) {
  *charged = false;
  long long overflow = OverflowBytes();
  if (overflow > 0 && !g_mem_soft) {
    std::fprintf(stderr,
                 "tpushim: tpushare: HBM cap exceeded: pod is %lld bytes "
                 "over its gpu_mem cap (executable outputs); %lld-byte %s "
                 "denied\n", overflow, bytes, what);
    return false;
  }
  int rc = tpushare_mem_request(bytes);
  *charged = rc > 0;
  if (rc == 0) {  // broker DENY; rc<0 (broker gone) fails open
    if (!g_mem_soft) {
      std::fprintf(stderr,
                   "tpushim: tpushare: HBM cap exceeded: %lld-byte %s "
                   "denied (pod over its gpu_mem cap)\n", bytes, what);
      return false;
    }
    std::fprintf(stderr,
                 "tpushim: HBM cap exceeded by %lld-byte %s (soft mode; "
                 "not denied)\n", bytes, what);
  }
  return true;
}

PJRT_Error* (*g_real_create_async_buffers)(
    PJRT_Client_CreateBuffersForAsyncHostToDevice_Args*) = nullptr;
PJRT_Error* (*g_real_tm_retrieve)(
    PJRT_AsyncHostToDeviceTransferManager_RetrieveBuffer_Args*) = nullptr;
PJRT_Error* (*g_real_tm_destroy)(
    PJRT_AsyncHostToDeviceTransferManager_Destroy_Args*) = nullptr;
PJRT_Error* (*g_real_dma_map)(PJRT_Client_DmaMap_Args*) = nullptr;
PJRT_Error* (*g_real_dma_unmap)(PJRT_Client_DmaUnmap_Args*) = nullptr;

PJRT_Error* HookedCreateBuffersForAsyncH2D(
    PJRT_Client_CreateBuffersForAsyncHostToDevice_Args* args) {
  if (!g_gated || args->shape_specs == nullptr) {
    return g_real_create_async_buffers(args);
  }
  std::vector<long long> sizes;
  long long total = 0;
  for (size_t i = 0; i < args->num_shape_specs; i++) {
    const PJRT_ShapeSpec& spec = args->shape_specs[i];
    long long elements = 1;
    for (size_t d = 0; d < spec.num_dims; d++) elements *= spec.dims[d];
    long long bytes = elements * ElementBytes(spec.element_type);
    sizes.push_back(bytes);
    total += bytes;
  }
  bool charged = false;
  if (!ChargeUploadBytes(total, "async host-to-device allocation",
                         &charged)) {
    return MakeShimError(
        PJRT_Error_Code_RESOURCE_EXHAUSTED,
        "tpushare: HBM cap exceeded: async host-to-device allocation "
        "denied (pod over its gpu_mem cap)");
  }
  PJRT_Error* err = g_real_create_async_buffers(args);
  if (err == nullptr && args->transfer_manager != nullptr && charged) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    TransferManagerCharge& tm = TransferManagers()[args->transfer_manager];
    tm.per_buffer = std::move(sizes);
    tm.remaining = total;
  } else if (err != nullptr && charged) {
    tpushare_mem_request(-total);  // create failed: roll the charge back
  }
  return err;
}

PJRT_Error* HookedAsyncH2DRetrieveBuffer(
    PJRT_AsyncHostToDeviceTransferManager_RetrieveBuffer_Args* args) {
  PJRT_Error* err = g_real_tm_retrieve(args);
  if (g_gated && err == nullptr && args->buffer_out != nullptr) {
    // hand the buffer's share of the create-time charge to the regular
    // ledger: from here on Buffer_Destroy credits it like any upload
    std::lock_guard<std::mutex> lock(g_mem_mu);
    auto it = TransferManagers().find(args->transfer_manager);
    if (it != TransferManagers().end()) {
      TransferManagerCharge& tm = it->second;
      int idx = args->buffer_index;
      if (idx >= 0 && static_cast<size_t>(idx) < tm.per_buffer.size() &&
          tm.per_buffer[idx] > 0) {
        ChargedBuffers()[args->buffer_out] += tm.per_buffer[idx];
        tm.remaining -= tm.per_buffer[idx];
        tm.per_buffer[idx] = -1;  // first retrieve transfers ownership
      }
    }
  }
  return err;
}

PJRT_Error* HookedAsyncH2DDestroy(
    PJRT_AsyncHostToDeviceTransferManager_Destroy_Args* args) {
  if (g_gated && args->transfer_manager != nullptr) {
    long long credit = 0;
    {
      std::lock_guard<std::mutex> lock(g_mem_mu);
      auto it = TransferManagers().find(args->transfer_manager);
      if (it != TransferManagers().end()) {
        credit = it->second.remaining;
        TransferManagers().erase(it);
      }
    }
    // unretrieved buffers die with the manager; retrieved ones live on
    // and are credited by their own Buffer_Destroy
    if (credit > 0) tpushare_mem_request(-credit);
  }
  return g_real_tm_destroy(args);
}

PJRT_Error* HookedDmaMap(PJRT_Client_DmaMap_Args* args) {
  if (!g_gated) return g_real_dma_map(args);
  long long bytes = static_cast<long long>(args->size);
  bool charged = false;
  if (!ChargeUploadBytes(bytes, "dma mapping", &charged)) {
    return MakeShimError(
        PJRT_Error_Code_RESOURCE_EXHAUSTED,
        "tpushare: HBM cap exceeded: dma mapping denied (pod over its "
        "gpu_mem cap)");
  }
  PJRT_Error* err = g_real_dma_map(args);
  if (err == nullptr && charged && args->data != nullptr) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    DmaMapped()[args->data] += bytes;
  } else if (err != nullptr && charged) {
    tpushare_mem_request(-bytes);
  }
  return err;
}

// Device-to-device copy: PJRT_Buffer_CopyToDevice allocates a same-size
// buffer on the destination device — HBM that passes no host->device
// hook.  The only pre-copy observable is the SOURCE buffer's on-device
// size, which equals the target's; charge it like an upload (deny
// before the device allocates) and put the target on the per-buffer
// ledger so its destroy credits exactly the charge.
PJRT_Error* (*g_real_copy_to_device)(PJRT_Buffer_CopyToDevice_Args*) =
    nullptr;
PJRT_Error* (*g_real_create_view)(
    PJRT_Client_CreateViewOfDeviceBuffer_Args*) = nullptr;

long long BufferDeviceBytes(PJRT_Buffer* buffer);  // defined below

PJRT_Error* HookedCopyToDevice(PJRT_Buffer_CopyToDevice_Args* args) {
  if (!g_gated) return g_real_copy_to_device(args);
  long long bytes = BufferDeviceBytes(args->buffer);
  bool charged = false;
  if (bytes > 0 &&
      !ChargeUploadBytes(bytes, "device-to-device copy", &charged)) {
    return MakeShimError(
        PJRT_Error_Code_RESOURCE_EXHAUSTED,
        "tpushare: HBM cap exceeded: device-to-device copy denied (pod "
        "over its gpu_mem cap)");
  }
  PJRT_Error* err = g_real_copy_to_device(args);
  if (err == nullptr && charged && args->dst_buffer != nullptr) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    ChargedBuffers()[args->dst_buffer] += bytes;
  } else if (err != nullptr && charged) {
    tpushare_mem_request(-bytes);  // copy failed: roll the charge back
  }
  return err;
}

// Aliased view: the wrapped device memory was allocated (and, when it
// came through a hooked path, already charged) by someone else — a view
// is explicitly ZERO-size on the ledger.  Recording it at 0 pins two
// invariants: its destroy credits nothing (the credit>0 guard skips
// it), and an Execute output re-sighting finds it already accounted and
// cannot charge it as fresh HBM.
PJRT_Error* HookedCreateViewOfDeviceBuffer(
    PJRT_Client_CreateViewOfDeviceBuffer_Args* args) {
  PJRT_Error* err = g_real_create_view(args);
  if (g_gated && err == nullptr && args->buffer != nullptr) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    ChargedBuffers().emplace(args->buffer, 0);
  }
  return err;
}

PJRT_Error* HookedDmaUnmap(PJRT_Client_DmaUnmap_Args* args) {
  PJRT_Error* err = g_real_dma_unmap(args);
  if (g_gated && err == nullptr && args->data != nullptr) {
    long long credit = 0;
    {
      std::lock_guard<std::mutex> lock(g_mem_mu);
      auto it = DmaMapped().find(args->data);
      if (it != DmaMapped().end()) {
        credit = it->second;
        DmaMapped().erase(it);
      }
    }
    if (credit > 0) tpushare_mem_request(-credit);
  }
  return err;
}

// -----------------------------------------------------------------------
// Executable output accounting: outputs allocate HBM without passing any
// host->device hook, so Execute charges them on first sighting.  The
// per-LoadedExecutable output count comes from GetExecutable →
// NumOutputs, cached after the first lookup.
// -----------------------------------------------------------------------

std::unordered_map<const void*, size_t>& NumOutputsCache() {
  static auto* cache =
      new std::unordered_map<const void*, size_t>;  // guarded by g_mem_mu
  return *cache;  // leaked: see RetiredEvents
}

bool LookupNumOutputs(PJRT_LoadedExecutable* loaded, size_t* num_outputs) {
  if (loaded == nullptr || g_real_get_executable == nullptr ||
      g_real_executable_num_outputs == nullptr) {
    return false;
  }
  {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    auto it = NumOutputsCache().find(loaded);
    if (it != NumOutputsCache().end()) {
      *num_outputs = it->second;
      return true;
    }
  }
  PJRT_LoadedExecutable_GetExecutable_Args get_args;
  std::memset(&get_args, 0, sizeof(get_args));
  get_args.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  get_args.loaded_executable = loaded;
  if (PJRT_Error* err = g_real_get_executable(&get_args)) {
    DestroyRealError(err);
    return false;
  }
  PJRT_Executable_NumOutputs_Args num_args;
  std::memset(&num_args, 0, sizeof(num_args));
  num_args.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  num_args.executable = get_args.executable;
  PJRT_Error* err = g_real_executable_num_outputs(&num_args);
  bool ok = err == nullptr;
  if (err != nullptr) DestroyRealError(err);
  if (g_real_executable_destroy != nullptr && get_args.executable != nullptr) {
    PJRT_Executable_Destroy_Args destroy_args;
    std::memset(&destroy_args, 0, sizeof(destroy_args));
    destroy_args.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    destroy_args.executable = get_args.executable;
    if (PJRT_Error* destroy_err = g_real_executable_destroy(&destroy_args)) {
      DestroyRealError(destroy_err);
    }
  }
  if (!ok) return false;
  *num_outputs = num_args.num_outputs;
  std::lock_guard<std::mutex> lock(g_mem_mu);
  NumOutputsCache()[loaded] = num_args.num_outputs;
  return true;
}

long long BufferDeviceBytes(PJRT_Buffer* buffer) {
  if (buffer == nullptr || g_real_buffer_size == nullptr) return -1;
  PJRT_Buffer_OnDeviceSizeInBytes_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_OnDeviceSizeInBytes_Args_STRUCT_SIZE;
  args.buffer = buffer;
  if (PJRT_Error* err = g_real_buffer_size(&args)) {
    DestroyRealError(err);
    return -1;
  }
  return static_cast<long long>(args.on_device_size_in_bytes);
}

// Charge one output buffer against the cap (first sighting only).  A
// broker DENY moves the bytes onto the local overflow ledger — the
// allocation already happened on-device, so the accounting must record
// it even though it exceeds the cap; subsequent uploads/executes are
// what get denied.
void ChargeOutputBuffer(PJRT_Buffer* buffer) {
  if (buffer == nullptr) return;
  {
    // dedup before the plugin size query: re-sighted (donated-alias)
    // buffers on the per-step hot path cost no plugin round trip
    std::lock_guard<std::mutex> lock(g_mem_mu);
    if (ChargedBuffers().count(buffer) != 0 ||
        OverflowBuffers().count(buffer) != 0) {
      return;
    }
  }
  long long bytes = BufferDeviceBytes(buffer);
  if (bytes <= 0) return;
  int rc = tpushare_mem_request(bytes);
  std::lock_guard<std::mutex> lock(g_mem_mu);
  if (rc > 0) {
    ChargedBuffers()[buffer] += bytes;
  } else if (rc == 0) {
    OverflowBuffers()[buffer] += bytes;
    g_overflow_bytes += bytes;
    std::fprintf(stderr,
                 "tpushim: HBM cap exceeded: %lld-byte executable output "
                 "puts pod %lld bytes over its gpu_mem cap%s\n",
                 bytes, g_overflow_bytes,
                 g_mem_soft ? " (soft mode)" : "; further uploads/executes "
                                               "will be denied");
  }  // rc < 0: broker gone, fail open
}

// Invalidate the cached output count when a loaded executable dies: its
// address can be reused by a later executable with a different count, and
// a stale count would walk past the caller's output_lists.
PJRT_Error* HookedLoadedExecutableDestroy(
    PJRT_LoadedExecutable_Destroy_Args* args) {
  if (args->executable != nullptr) {
    std::lock_guard<std::mutex> lock(g_mem_mu);
    NumOutputsCache().erase(args->executable);
  }
  return g_real_loaded_destroy(args);
}

void ChargeExecuteOutputs(PJRT_LoadedExecutable_Execute_Args* args) {
  // same old-struct guard the events path applies: a caller compiled
  // against an older header may end before output_lists
  if (args->struct_size < PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE) {
    return;
  }
  if (args->output_lists == nullptr) return;
  size_t num_outputs = 0;
  if (!LookupNumOutputs(args->executable, &num_outputs)) return;
  for (size_t d = 0; d < args->num_devices; d++) {
    PJRT_Buffer** device_outputs = args->output_lists[d];
    if (device_outputs == nullptr) continue;
    for (size_t o = 0; o < num_outputs; o++) {
      ChargeOutputBuffer(device_outputs[o]);
    }
  }
}

// ---------------------------------------------------------------------------
// Execute: token-gated, charged by device completion time.
// ---------------------------------------------------------------------------

double NowMs() {
  return std::chrono::duration<double, std::milli>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

std::mutex g_charge_mu;
double g_estimate_ms = 1.0;       // EMA of observed device time (estimate only)
double g_last_complete_ms = 0.0;  // completion-to-completion charging anchor

// Events we own whose callbacks have fired; destroyed on the next Execute
// (never from inside the plugin's callback thread).
std::vector<PJRT_Event*>& RetiredEvents() {
  // intentionally leaked (like every container the runtime's completion
  // callback thread can touch): OnExecuteComplete may fire after main
  // returns, and a destroyed static here is a use-after-free at exit
  static auto* retired = new std::vector<PJRT_Event*>;
  return *retired;
}

void DrainRetiredEventsLocked() {
  std::vector<PJRT_Event*> retired;
  {
    std::lock_guard<std::mutex> lock(g_charge_mu);
    retired.swap(RetiredEvents());
  }
  for (PJRT_Event* event : retired) {
    PJRT_Event_Destroy_Args destroy_args;
    std::memset(&destroy_args, 0, sizeof(destroy_args));
    destroy_args.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    destroy_args.event = event;
    DestroyRealError(g_real_event_destroy(&destroy_args));
  }
}

void ChargeCompletion(double start_ms, double ready_ms) {
  double charged;
  {
    std::lock_guard<std::mutex> lock(g_charge_mu);
    double base = g_last_complete_ms > start_ms ? g_last_complete_ms : start_ms;
    charged = ready_ms - base;
    if (charged < 0.0) charged = 0.0;
    if (ready_ms > g_last_complete_ms) g_last_complete_ms = ready_ms;
    g_estimate_ms = 0.8 * g_estimate_ms + 0.2 * charged;
  }
  tpushare_release(charged);
}

struct ExecCharge {
  double start_ms;
  PJRT_Event* event;
  bool owned;    // we allocated the event (caller passed no events array)
  bool primary;  // device 0 carries the charge for the execution
};

void OnExecuteComplete(PJRT_Error* error, void* user_arg) {
  auto* charge = static_cast<ExecCharge*>(user_arg);
  DestroyRealError(error);
  if (charge->primary) ChargeCompletion(charge->start_ms, NowMs());
  if (charge->owned) {
    std::lock_guard<std::mutex> lock(g_charge_mu);
    RetiredEvents().push_back(charge->event);
  }
  delete charge;
}

PJRT_Error* HookedExecute(PJRT_LoadedExecutable_Execute_Args* args) {
  if (!g_gated) return g_real_execute(args);
  long long overflow = OverflowBytes();
  if (overflow > 0 && !g_mem_soft) {
    // over cap via executable outputs: executing would allocate more
    // output HBM, so refuse until destroys clear the overflow
    char msg[200];
    std::snprintf(msg, sizeof(msg),
                  "tpushare: HBM cap exceeded: pod is %lld bytes over its "
                  "gpu_mem cap (executable outputs); execute denied",
                  overflow);
    std::fprintf(stderr, "tpushim: %s\n", msg);
    return MakeShimError(PJRT_Error_Code_RESOURCE_EXHAUSTED, msg);
  }
  double estimate;
  {
    std::lock_guard<std::mutex> lock(g_charge_mu);
    estimate = g_estimate_ms;
  }
  tpushare_acquire(estimate);
  DrainRetiredEventsLocked();

  // ask the plugin for completion events when the caller didn't
  bool events_usable =
      g_real_event_on_ready != nullptr && g_real_event_destroy != nullptr &&
      args->struct_size >= PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE &&
      args->num_devices >= 1;
  std::vector<PJRT_Event*> own_events;
  bool own = false;
  if (events_usable && args->device_complete_events == nullptr) {
    own_events.assign(args->num_devices, nullptr);
    args->device_complete_events = own_events.data();
    own = true;
  }

  double start = NowMs();
  PJRT_Error* err = g_real_execute(args);
  double dispatch_end = NowMs();

  // account the output buffers this execution allocated (charge on first
  // sighting; credited when HookedBufferDestroy sees them)
  if (err == nullptr) ChargeExecuteOutputs(args);

  if (err != nullptr && own) {
    // per spec the plugin does not populate events on error, but a plugin
    // that filled some before failing must not leak them
    for (size_t i = 0; i < args->num_devices; i++) {
      if (own_events[i] != nullptr) {
        std::lock_guard<std::mutex> lock(g_charge_mu);
        RetiredEvents().push_back(own_events[i]);
      }
    }
  }

  bool charged_async = false;
  if (err == nullptr && events_usable &&
      args->device_complete_events != nullptr) {
    for (size_t i = 0; i < args->num_devices; i++) {
      PJRT_Event* event = args->device_complete_events[i];
      if (event == nullptr) continue;
      auto* charge = new ExecCharge{start, event, own, i == 0};
      PJRT_Event_OnReady_Args ready_args;
      std::memset(&ready_args, 0, sizeof(ready_args));
      ready_args.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
      ready_args.event = event;
      ready_args.callback = OnExecuteComplete;
      ready_args.user_arg = charge;
      PJRT_Error* ready_err = g_real_event_on_ready(&ready_args);
      if (ready_err != nullptr) {
        DestroyRealError(ready_err);
        delete charge;
        if (own) {
          std::lock_guard<std::mutex> lock(g_charge_mu);
          RetiredEvents().push_back(event);
        }
        continue;
      }
      if (i == 0) charged_async = true;
    }
  }
  if (own) args->device_complete_events = nullptr;  // restore caller's view

  if (!charged_async) {
    // no events available (old runtime / execute error): dispatch wall time
    // is the only observable — the documented lower bound
    double elapsed = dispatch_end - start;
    {
      std::lock_guard<std::mutex> lock(g_charge_mu);
      g_estimate_ms = 0.8 * g_estimate_ms + 0.2 * elapsed;
    }
    tpushare_release(elapsed);
  }
  return err;
}

// ---------------------------------------------------------------------------
// Client create: client-init preallocation is the one allocation the
// per-buffer hooks can never see (SURVEY §7.4) — the plugin may grab its
// whole HBM share inside PJRT_Client_Create.  Inject allocator-cap create
// options derived from TPUSHARE_MEM_FRACTION; if the plugin rejects the
// (platform-specific) options, retry bare — enforcement falls back to the
// upload/output ledger rather than failing the client.
// ---------------------------------------------------------------------------

PJRT_Error* HookedClientCreate(PJRT_Client_Create_Args* args) {
  double fraction = MemFraction();
  if (fraction <= 0.0) return g_real_client_create(args);

  std::vector<PJRT_NamedValue> options(
      args->create_options, args->create_options + args->num_options);
  bool has_fraction = false, has_preallocate = false;
  for (const PJRT_NamedValue& option : options) {
    std::string name(option.name, option.name_size);
    if (name == "memory_fraction") has_fraction = true;
    if (name == "preallocate") has_preallocate = true;
  }
  if (has_fraction && has_preallocate) return g_real_client_create(args);

  if (!has_fraction) {
    PJRT_NamedValue fraction_option;
    std::memset(&fraction_option, 0, sizeof(fraction_option));
    fraction_option.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    fraction_option.name = "memory_fraction";
    fraction_option.name_size = std::strlen("memory_fraction");
    fraction_option.type = PJRT_NamedValue_kFloat;
    fraction_option.float_value = static_cast<float>(fraction);
    fraction_option.value_size = 1;
    options.push_back(fraction_option);
  }
  if (!has_preallocate) {
    // preallocation off: co-tenants must be able to start in any order
    PJRT_NamedValue preallocate_option;
    std::memset(&preallocate_option, 0, sizeof(preallocate_option));
    preallocate_option.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    preallocate_option.name = "preallocate";
    preallocate_option.name_size = std::strlen("preallocate");
    preallocate_option.type = PJRT_NamedValue_kBool;
    preallocate_option.bool_value = false;
    preallocate_option.value_size = 1;
    options.push_back(preallocate_option);
  }

  const PJRT_NamedValue* original_options = args->create_options;
  size_t original_num = args->num_options;
  args->create_options = options.data();
  args->num_options = options.size();
  PJRT_Error* err = g_real_client_create(args);
  args->create_options = original_options;
  args->num_options = original_num;
  if (err == nullptr) {
    std::fprintf(stderr,
                 "tpushim: client created with memory_fraction=%.4f "
                 "preallocate=false\n", fraction);
    return nullptr;
  }
  // Retry bare only when the failure looks like option rejection
  // (INVALID_ARGUMENT / UNIMPLEMENTED, or unreadable code on an old
  // plugin).  Any other failure — OOM, transient init error — is the
  // caller's to see: a blind retry would destroy the original error and
  // hand a partially-initialized plugin a second create.
  int code = RealErrorCode(err);
  bool option_rejection = code < 0 ||
                          code == PJRT_Error_Code_INVALID_ARGUMENT ||
                          code == PJRT_Error_Code_UNIMPLEMENTED;
  if (!option_rejection) return err;
  DestroyRealError(err);
  std::fprintf(stderr,
               "tpushim: plugin rejected allocator-cap create options "
               "(code %d), retrying without them (cap enforced by "
               "upload/output accounting only)\n", code);
  return g_real_client_create(args);
}

// Client destroy releases every buffer the client owns without a
// per-buffer PJRT_Buffer_Destroy, so the ledgers must be settled here or
// a pod that re-creates its client stays charged (and, in hard mode,
// permanently denied once over cap).  The shim gates a single plugin and
// in practice a single client; with several live clients this over-credits
// transiently, which the broker clamps at zero (tokend Mem(): next < 0 ->
// 0), so the failure mode is brief under-counting, never a stuck denial.
PJRT_Error* HookedClientDestroy(PJRT_Client_Destroy_Args* args) {
  if (g_gated) {
    long long credit = 0;
    {
      std::lock_guard<std::mutex> lock(g_mem_mu);
      for (const auto& kv : ChargedBuffers()) credit += kv.second;
      ChargedBuffers().clear();
      OverflowBuffers().clear();
      g_overflow_bytes = 0;
      NumOutputsCache().clear();
      // transfer managers and dma mappings die with their client too
      for (const auto& kv : TransferManagers()) credit += kv.second.remaining;
      TransferManagers().clear();
      for (const auto& kv : DmaMapped()) credit += kv.second;
      DmaMapped().clear();
    }
    if (credit > 0) tpushare_mem_request(-credit);
  }
  return g_real_client_destroy(args);
}

// ---------------------------------------------------------------------------
// API table wrapping.
// ---------------------------------------------------------------------------

const PJRT_Api* WrapApi(const PJRT_Api* real) {
  static std::mutex mu;
  static const PJRT_Api* wrapped_source = nullptr;
  static PJRT_Api wrapped;
  if (real == nullptr) return nullptr;
  std::lock_guard<std::mutex> lock(mu);
  if (wrapped_source == real) return &wrapped;
  if (wrapped_source != nullptr) {
    // a second distinct plugin in this process: pass through unhooked
    // rather than misrouting its calls into the first plugin's table
    std::fprintf(stderr,
                 "tpushim: additional PJRT plugin detected, not gating it\n");
    return real;
  }
  // one line per process: which PJRT C API this shim was compiled against
  // and which one the runtime it wraps reports (a size or version gap here
  // is the first thing to look at when gating misbehaves)
  std::fprintf(stderr,
               "tpushim: header PJRT %d.%d (api struct %zu), runtime PJRT "
               "%d.%d (api struct %zu)\n",
               PJRT_API_MAJOR, PJRT_API_MINOR,
               static_cast<size_t>(PJRT_Api_STRUCT_SIZE),
               real->pjrt_api_version.major_version,
               real->pjrt_api_version.minor_version, real->struct_size);
  if (real->struct_size < PJRT_Api_STRUCT_SIZE) {
    // runtime older than our header: pass through unhooked
    std::fprintf(stderr,
                 "tpushim: PJRT api struct too small (%zu), not gating\n",
                 real->struct_size);
    return real;
  }
  std::memcpy(&wrapped, real, sizeof(PJRT_Api));
  g_real_execute = wrapped.PJRT_LoadedExecutable_Execute;
  wrapped.PJRT_LoadedExecutable_Execute = HookedExecute;
  g_real_buffer_from_host = wrapped.PJRT_Client_BufferFromHostBuffer;
  g_real_buffer_destroy = wrapped.PJRT_Buffer_Destroy;
  g_real_error_destroy = wrapped.PJRT_Error_Destroy;
  g_real_error_message = wrapped.PJRT_Error_Message;
  g_real_error_get_code = wrapped.PJRT_Error_GetCode;
  g_real_event_on_ready = wrapped.PJRT_Event_OnReady;
  g_real_event_destroy = wrapped.PJRT_Event_Destroy;
  g_real_client_create = wrapped.PJRT_Client_Create;
  g_real_client_destroy = wrapped.PJRT_Client_Destroy;
  g_real_buffer_size = wrapped.PJRT_Buffer_OnDeviceSizeInBytes;
  g_real_get_executable = wrapped.PJRT_LoadedExecutable_GetExecutable;
  g_real_executable_num_outputs = wrapped.PJRT_Executable_NumOutputs;
  g_real_executable_destroy = wrapped.PJRT_Executable_Destroy;
  g_real_loaded_destroy = wrapped.PJRT_LoadedExecutable_Destroy;
  if (g_real_buffer_from_host != nullptr) {
    wrapped.PJRT_Client_BufferFromHostBuffer = HookedBufferFromHost;
  }
  if (g_real_buffer_destroy != nullptr) {
    wrapped.PJRT_Buffer_Destroy = HookedBufferDestroy;
  }
  if (g_real_client_create != nullptr) {
    wrapped.PJRT_Client_Create = HookedClientCreate;
  }
  if (g_real_client_destroy != nullptr) {
    wrapped.PJRT_Client_Destroy = HookedClientDestroy;
  }
  if (g_real_loaded_destroy != nullptr) {
    wrapped.PJRT_LoadedExecutable_Destroy = HookedLoadedExecutableDestroy;
  }
  // async host-to-device + dma-map alloc paths (VERDICT r4 #2)
  g_real_create_async_buffers =
      wrapped.PJRT_Client_CreateBuffersForAsyncHostToDevice;
  g_real_tm_retrieve =
      wrapped.PJRT_AsyncHostToDeviceTransferManager_RetrieveBuffer;
  g_real_tm_destroy = wrapped.PJRT_AsyncHostToDeviceTransferManager_Destroy;
  g_real_dma_map = wrapped.PJRT_Client_DmaMap;
  g_real_dma_unmap = wrapped.PJRT_Client_DmaUnmap;
  if (g_real_create_async_buffers != nullptr) {
    wrapped.PJRT_Client_CreateBuffersForAsyncHostToDevice =
        HookedCreateBuffersForAsyncH2D;
  }
  if (g_real_tm_retrieve != nullptr) {
    wrapped.PJRT_AsyncHostToDeviceTransferManager_RetrieveBuffer =
        HookedAsyncH2DRetrieveBuffer;
  }
  if (g_real_tm_destroy != nullptr) {
    wrapped.PJRT_AsyncHostToDeviceTransferManager_Destroy =
        HookedAsyncH2DDestroy;
  }
  if (g_real_dma_map != nullptr) {
    wrapped.PJRT_Client_DmaMap = HookedDmaMap;
  }
  if (g_real_dma_unmap != nullptr) {
    wrapped.PJRT_Client_DmaUnmap = HookedDmaUnmap;
  }
  // device-to-device copy + aliased-view paths (VERDICT r5 #3/#4)
  g_real_copy_to_device = wrapped.PJRT_Buffer_CopyToDevice;
  g_real_create_view = wrapped.PJRT_Client_CreateViewOfDeviceBuffer;
  if (g_real_copy_to_device != nullptr) {
    wrapped.PJRT_Buffer_CopyToDevice = HookedCopyToDevice;
  }
  if (g_real_create_view != nullptr) {
    wrapped.PJRT_Client_CreateViewOfDeviceBuffer =
        HookedCreateViewOfDeviceBuffer;
  }
  // fabricated-error service entries (pass-through for real errors)
  wrapped.PJRT_Error_Destroy = HookedErrorDestroy;
  wrapped.PJRT_Error_Message = HookedErrorMessage;
  wrapped.PJRT_Error_GetCode = HookedErrorGetCode;
  const char* mode = std::getenv("TPUSHARE_MEM_ENFORCE");
  g_mem_soft = mode != nullptr && std::strcmp(mode, "soft") == 0;
  g_gated = tpushare_init_from_env() == 0;
  if (!g_gated) {
    std::fprintf(stderr, "tpushim: no POD_MANAGER_PORT, running ungated\n");
  }
  wrapped_source = real;
  return &wrapped;
}

GetPjrtApiFn RealGetPjrtApi() {
  static GetPjrtApiFn real = reinterpret_cast<GetPjrtApiFn>(
      dlsym(RTLD_NEXT, "GetPjrtApi"));
  return real;
}

// Runs when the shim is LD_PRELOADed, before the interpreter (and any
// JAX/XLA client) starts: translate TPUSHARE_MEM_FRACTION into the XLA
// allocator env the way kubeshare_tpu.isolation.guard.apply_hbm_cap does
// in-process, so a preload-only pod (no guard import) still gets its
// client allocator capped at create time.  setenv(no-overwrite) keeps any
// operator-set value authoritative.
__attribute__((constructor)) void ExportAllocatorEnv() {
  double fraction = MemFraction();
  if (fraction <= 0.0) return;
  char value[32];
  std::snprintf(value, sizeof(value), "%.4f", fraction);
  setenv("XLA_PYTHON_CLIENT_MEM_FRACTION", value, /*overwrite=*/0);
  setenv("XLA_PYTHON_CLIENT_PREALLOCATE", "false", /*overwrite=*/0);
}

}  // namespace

extern "C" {

// Path 1: direct symbol interposition.
const PJRT_Api* GetPjrtApi(void) {
  GetPjrtApiFn real = RealGetPjrtApi();
  if (real == nullptr) return nullptr;
  return WrapApi(real());
}

// Path 2: dlsym interposition for dlopen'd plugins (libtpu.so).
// The real dlsym is resolved via dlvsym (which we do not interpose) against
// the known glibc symbol versions.
static GetPjrtApiFn g_plugin_get_api = nullptr;

static const PJRT_Api* DlsymGetPjrtApiTrampoline(void) {
  if (g_plugin_get_api == nullptr) return nullptr;
  return WrapApi(g_plugin_get_api());
}

typedef void* (*DlsymFn)(void*, const char*);

static DlsymFn ResolveRealDlsym(void) {
  static DlsymFn real = nullptr;
  if (real != nullptr) return real;
  for (const char* version : {"GLIBC_2.34", "GLIBC_2.2.5", "GLIBC_2.17"}) {
    real = reinterpret_cast<DlsymFn>(dlvsym(RTLD_NEXT, "dlsym", version));
    if (real != nullptr) return real;
  }
  return nullptr;
}

void* dlsym(void* handle, const char* name) {
  DlsymFn real_dlsym = ResolveRealDlsym();
  if (real_dlsym == nullptr) return nullptr;  // cannot resolve: fail lookup
  void* symbol = real_dlsym(handle, name);
  if (symbol != nullptr && name != nullptr &&
      std::strcmp(name, "GetPjrtApi") == 0) {
    g_plugin_get_api = reinterpret_cast<GetPjrtApiFn>(symbol);
    return reinterpret_cast<void*>(&DlsymGetPjrtApiTrampoline);
  }
  return symbol;
}

}  // extern "C"
