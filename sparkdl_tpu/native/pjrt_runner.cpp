// pjrt_runner.cpp — native PJRT driver: the second-stack executor.
//
// Role (SURVEY.md §2 "Native components" / §3.5): the reference kept a
// non-Python featurizer stack — Scala `DeepImageFeaturizer` running frozen
// GraphDefs through TensorFrames' JNI bridge into the TF C++ runtime
// (`src/main/scala/com/databricks/sparkdl/DeepImageFeaturizer.scala`†).
// This file is that stack's TPU-native analog: C++ that dlopens a PJRT
// plugin (any GetPjrtApi shared object), compiles a serialized StableHLO
// program (the frozen-GraphDef analog exported by
// `sparkdl_tpu.graph.XlaFunction`), holds params device-resident, and
// streams batches through `PJRT_LoadedExecutable_Execute` — no Python in
// the loop.
//
// Exposes a small C ABI (handles + error strings) consumed two ways:
//   1. ctypes from `sparkdl_tpu/native/pjrt.py` (in-process bridge);
//   2. the standalone featurizer CLI in `pjrt_tool.cpp` (true dual stack).
//
// Build: g++ -O2 -std=c++17 -fPIC -shared -I<tf-include> -o _pjrt_runner.so
//        pjrt_runner.cpp -ldl    (driven by native/__init__.py)

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Runner {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;  // first addressable device
  std::mutex mu;
  int64_t next_id = 1;
  std::unordered_map<int64_t, PJRT_LoadedExecutable*> execs;
  std::unordered_map<int64_t, size_t> exec_num_outputs;
  std::unordered_map<int64_t, PJRT_Buffer*> buffers;
  std::string last_error;
};

void set_err(Runner* r, const std::string& msg) {
  if (r) r->last_error = msg;
}

// Returns true when `err` is non-null (an error), records the message.
bool take_error(Runner* r, PJRT_Error* err, const char* where) {
  if (!err) return false;
  std::string msg = where;
  msg += ": ";
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  r->api->PJRT_Error_Message(&margs);
  msg.append(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  r->api->PJRT_Error_Destroy(&dargs);
  set_err(r, msg);
  return true;
}

bool await_event(Runner* r, PJRT_Event* ev, const char* where) {
  if (!ev) return true;
  PJRT_Event_Await_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* err = r->api->PJRT_Event_Await(&aargs);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  r->api->PJRT_Event_Destroy(&dargs);
  return !take_error(r, err, where);
}

bool dtype_to_pjrt(const char* dtype, PJRT_Buffer_Type* out,
                   size_t* itemsize) {
  struct Entry {
    const char* name;
    PJRT_Buffer_Type type;
    size_t size;
  };
  static const Entry table[] = {
      {"f32", PJRT_Buffer_Type_F32, 4},  {"f16", PJRT_Buffer_Type_F16, 2},
      {"bf16", PJRT_Buffer_Type_BF16, 2}, {"f64", PJRT_Buffer_Type_F64, 8},
      {"u8", PJRT_Buffer_Type_U8, 1},    {"s8", PJRT_Buffer_Type_S8, 1},
      {"s32", PJRT_Buffer_Type_S32, 4},  {"s64", PJRT_Buffer_Type_S64, 8},
      {"u32", PJRT_Buffer_Type_U32, 4},  {"u64", PJRT_Buffer_Type_U64, 8},
      {"s16", PJRT_Buffer_Type_S16, 2},  {"u16", PJRT_Buffer_Type_U16, 2},
      {"pred", PJRT_Buffer_Type_PRED, 1},
  };
  for (const auto& e : table) {
    if (std::strcmp(dtype, e.name) == 0) {
      *out = e.type;
      *itemsize = e.size;
      return true;
    }
  }
  return false;
}

}  // namespace

extern "C" {

// Create a runner: dlopen `plugin_path`, GetPjrtApi, initialize the plugin,
// create a client.  `keys`/`str_vals`/`int_vals`/`is_int` describe
// `n_options` PJRT_NamedValue client-create options (a key uses
// str_vals[i] when is_int[i]==0, else int_vals[i]) — for a plugin that
// refuses a bare create.  Returns nullptr on
// failure with the message in `err`/`err_len` (when provided).
Runner* pjrt_runner_create_opts(const char* plugin_path, const char** keys,
                                const char** str_vals,
                                const int64_t* int_vals,
                                const int32_t* is_int, int32_t n_options,
                                char* err, int err_len) {
  auto fail = [&](const std::string& msg) -> Runner* {
    if (err && err_len > 0) {
      std::snprintf(err, err_len, "%s", msg.c_str());
    }
    return nullptr;
  };
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) return fail(std::string("dlopen failed: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    dlclose(dl);
    return fail("plugin has no GetPjrtApi symbol");
  }
  const PJRT_Api* api = get_api();
  if (!api) {
    dlclose(dl);
    return fail("GetPjrtApi returned null");
  }

  Runner* r = new Runner();
  r->dl = dl;
  r->api = api;

  PJRT_Plugin_Initialize_Args iargs;
  std::memset(&iargs, 0, sizeof(iargs));
  iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (take_error(r, api->PJRT_Plugin_Initialize(&iargs),
                 "PJRT_Plugin_Initialize")) {
    std::string msg = r->last_error;
    delete r;
    dlclose(dl);
    return fail(msg);
  }

  std::vector<PJRT_NamedValue> options(
      static_cast<size_t>(n_options > 0 ? n_options : 0));
  for (int32_t i = 0; i < n_options; ++i) {
    PJRT_NamedValue& nv = options[i];
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = keys[i];
    nv.name_size = std::strlen(keys[i]);
    if (is_int[i]) {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = int_vals[i];
      nv.value_size = 1;
    } else {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = str_vals[i];
      nv.value_size = std::strlen(str_vals[i]);
    }
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = options.empty() ? nullptr : options.data();
  cargs.num_options = options.size();
  if (take_error(r, api->PJRT_Client_Create(&cargs), "PJRT_Client_Create")) {
    std::string msg = r->last_error;
    delete r;
    dlclose(dl);
    return fail(msg);
  }
  r->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = r->client;
  if (take_error(r, api->PJRT_Client_AddressableDevices(&dargs),
                 "PJRT_Client_AddressableDevices") ||
      dargs.num_addressable_devices == 0) {
    std::string msg = r->last_error.empty() ? "no addressable devices"
                                            : r->last_error;
    delete r;  // leaks the client deliberately: plugin teardown on a failed
               // half-initialized state is riskier than a one-time leak
    return fail(msg);
  }
  r->device = dargs.addressable_devices[0];
  return r;
}

// Back-compat creator with no client options (plain plugins, e.g. CPU).
Runner* pjrt_runner_create(const char* plugin_path, char* err, int err_len) {
  return pjrt_runner_create_opts(plugin_path, nullptr, nullptr, nullptr,
                                 nullptr, 0, err, err_len);
}

const char* pjrt_runner_last_error(Runner* r) {
  return r ? r->last_error.c_str() : "null runner";
}

// Platform name (e.g. "tpu"); returns chars written (excluding NUL).
int pjrt_runner_platform(Runner* r, char* out, int out_len) {
  PJRT_Client_PlatformName_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = r->client;
  if (take_error(r, r->api->PJRT_Client_PlatformName(&args),
                 "PJRT_Client_PlatformName")) {
    return -1;
  }
  int n = static_cast<int>(args.platform_name_size);
  if (n >= out_len) n = out_len - 1;
  std::memcpy(out, args.platform_name, n);
  out[n] = '\0';
  return n;
}

// Compile StableHLO (MLIR text or bytecode).  `compile_options` is a
// serialized xla CompileOptionsProto (produced Python-side by
// jaxlib CompileOptions.SerializeAsString — shipped as a sidecar file so
// this library needs no protobuf dependency).  Returns an executable
// handle > 0, or -1 on error.
int64_t pjrt_runner_compile(Runner* r, const char* code, int64_t code_size,
                            const char* compile_options,
                            int64_t compile_options_size) {
  static const char kFormat[] = "mlir";
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = static_cast<size_t>(code_size);
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = r->client;
  args.program = &program;
  args.compile_options = compile_options;
  args.compile_options_size = static_cast<size_t>(compile_options_size);
  if (take_error(r, r->api->PJRT_Client_Compile(&args),
                 "PJRT_Client_Compile")) {
    return -1;
  }

  // The output count is load-bearing: execute sizes its output_lists from
  // it, so an unknown count must fail the compile, not default to 0 (the
  // plugin would write real output pointers past an empty array).
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = args.executable;
  size_t num_outputs = 0;
  bool have_count = false;
  if (!take_error(r, r->api->PJRT_LoadedExecutable_GetExecutable(&gargs),
                  "PJRT_LoadedExecutable_GetExecutable")) {
    PJRT_Executable_NumOutputs_Args nargs;
    std::memset(&nargs, 0, sizeof(nargs));
    nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    nargs.executable = gargs.executable;
    if (!take_error(r, r->api->PJRT_Executable_NumOutputs(&nargs),
                    "PJRT_Executable_NumOutputs")) {
      num_outputs = nargs.num_outputs;
      have_count = true;
    }
    PJRT_Executable_Destroy_Args xargs;
    std::memset(&xargs, 0, sizeof(xargs));
    xargs.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    xargs.executable = gargs.executable;
    take_error(r, r->api->PJRT_Executable_Destroy(&xargs),
               "PJRT_Executable_Destroy");
  }
  if (!have_count) {
    std::string msg = "compile: could not determine output count (" +
                      r->last_error + ")";
    PJRT_LoadedExecutable_Destroy_Args ldargs;
    std::memset(&ldargs, 0, sizeof(ldargs));
    ldargs.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    ldargs.executable = args.executable;
    take_error(r, r->api->PJRT_LoadedExecutable_Destroy(&ldargs),
               "PJRT_LoadedExecutable_Destroy");
    set_err(r, msg);
    return -1;
  }

  std::lock_guard<std::mutex> lock(r->mu);
  int64_t id = r->next_id++;
  r->execs[id] = args.executable;
  r->exec_num_outputs[id] = num_outputs;
  return id;
}

int64_t pjrt_runner_num_outputs(Runner* r, int64_t exec_id) {
  std::lock_guard<std::mutex> lock(r->mu);
  auto it = r->exec_num_outputs.find(exec_id);
  return it == r->exec_num_outputs.end() ? -1
                                         : static_cast<int64_t>(it->second);
}

// Shared host->device copy body; `semantics` selects sync
// (kImmutableUntilTransferCompletes — the await blocks until the
// transfer completes) vs async (kImmutableOnlyDuringCall — the plugin
// stages the bytes during the call, the await is ready at return, and
// the device transfer proceeds in the background).
static int64_t put_impl(Runner* r, const void* data, const char* dtype,
                        const int64_t* dims, int32_t num_dims,
                        PJRT_HostBufferSemantics semantics,
                        const char* what) {
  PJRT_Buffer_Type type;
  size_t itemsize;
  if (!dtype_to_pjrt(dtype, &type, &itemsize)) {
    set_err(r, std::string("unsupported dtype ") + dtype);
    return -1;
  }
  PJRT_Client_BufferFromHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = r->client;
  args.data = data;
  args.type = type;
  args.dims = dims;
  args.num_dims = static_cast<size_t>(num_dims);
  args.host_buffer_semantics = semantics;
  args.device = r->device;
  if (take_error(r, r->api->PJRT_Client_BufferFromHostBuffer(&args),
                 "PJRT_Client_BufferFromHostBuffer")) {
    return -1;
  }
  if (!await_event(r, args.done_with_host_buffer, what)) {
    return -1;
  }
  std::lock_guard<std::mutex> lock(r->mu);
  int64_t id = r->next_id++;
  r->buffers[id] = args.buffer;
  return id;
}

// Synchronously copy a dense host array to the device.  Returns a buffer
// handle > 0, or -1 on error.  `dtype` is one of the short names in
// dtype_to_pjrt ("f32", "u8", ...).
int64_t pjrt_runner_put(Runner* r, const void* data, const char* dtype,
                        const int64_t* dims, int32_t num_dims) {
  return put_impl(r, data, dtype, dims, num_dims,
                  PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes,
                  "host transfer");
}

// Asynchronous host->device copy: the plugin stages the host data during
// the call (kImmutableOnlyDuringCall), so `data` is reusable on return
// while the device-side transfer proceeds in the background.  Downstream
// consumers (execute, fetch) order themselves after the transfer via
// PJRT's buffer definition events — no host-side await needed.  This is
// the double-buffering primitive: batch i+1's transfer rides under batch
// i's execute instead of serializing before it (the TensorFrames
// "blocked pipelining" role — SURVEY.md §2 native table).
int64_t pjrt_runner_put_async(Runner* r, const void* data, const char* dtype,
                              const int64_t* dims, int32_t num_dims) {
  return put_impl(r, data, dtype, dims, num_dims,
                  PJRT_HostBufferSemantics_kImmutableOnlyDuringCall,
                  "host staging");
}

// Block until `buf_id`'s contents are defined on device (transfer or
// producing execution complete).  Surfaces asynchronous errors.
int pjrt_runner_await_buffer(Runner* r, int64_t buf_id) {
  PJRT_Buffer* buf;
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->buffers.find(buf_id);
    if (it == r->buffers.end()) {
      set_err(r, "bad buffer handle");
      return -1;
    }
    buf = it->second;
  }
  PJRT_Buffer_ReadyEvent_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ReadyEvent_Args_STRUCT_SIZE;
  args.buffer = buf;
  if (take_error(r, r->api->PJRT_Buffer_ReadyEvent(&args),
                 "PJRT_Buffer_ReadyEvent")) {
    return -1;
  }
  return await_event(r, args.event, "buffer ready") ? 0 : -1;
}

int pjrt_runner_free_buffer(Runner* r, int64_t buf_id) {
  PJRT_Buffer* buf = nullptr;
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->buffers.find(buf_id);
    if (it == r->buffers.end()) return -1;
    buf = it->second;
    r->buffers.erase(it);
  }
  PJRT_Buffer_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = buf;
  return take_error(r, r->api->PJRT_Buffer_Destroy(&args),
                    "PJRT_Buffer_Destroy")
             ? -1
             : 0;
}

// Shared execute body: `wait` controls whether the device-complete event
// is awaited (sync) or never requested (async — outputs become handles
// with pending definition events; fetch/await orders after compute).
static int64_t execute_impl(Runner* r, int64_t exec_id,
                            const int64_t* arg_buf_ids, int32_t num_args,
                            int64_t* out_buf_ids, bool wait) {
  PJRT_LoadedExecutable* exec;
  size_t num_outputs;
  std::vector<PJRT_Buffer*> args_vec(num_args);
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->execs.find(exec_id);
    if (it == r->execs.end()) {
      set_err(r, "bad executable handle");
      return -1;
    }
    exec = it->second;
    num_outputs = r->exec_num_outputs[exec_id];
    for (int32_t i = 0; i < num_args; ++i) {
      auto bit = r->buffers.find(arg_buf_ids[i]);
      if (bit == r->buffers.end()) {
        set_err(r, "bad buffer handle for argument " + std::to_string(i));
        return -1;
      }
      args_vec[i] = bit->second;
    }
  }

  PJRT_ExecuteOptions options;
  std::memset(&options, 0, sizeof(options));
  options.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  // No donation: exported programs carry no input_output_aliases (the
  // export path lowers without donate_argnums), so params stay resident.

  PJRT_Buffer* const* argument_list = args_vec.data();
  std::vector<PJRT_Buffer*> outputs(num_outputs, nullptr);
  PJRT_Buffer** output_list = outputs.data();
  PJRT_Event* device_complete = nullptr;

  PJRT_LoadedExecutable_Execute_Args eargs;
  std::memset(&eargs, 0, sizeof(eargs));
  eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  eargs.executable = exec;
  eargs.options = &options;
  eargs.argument_lists = &argument_list;
  eargs.num_devices = 1;
  eargs.num_args = static_cast<size_t>(num_args);
  eargs.output_lists = &output_list;
  eargs.device_complete_events = wait ? &device_complete : nullptr;
  if (take_error(r, r->api->PJRT_LoadedExecutable_Execute(&eargs),
                 "PJRT_LoadedExecutable_Execute")) {
    return -1;
  }
  if (wait && !await_event(r, device_complete, "execute")) return -1;

  std::lock_guard<std::mutex> lock(r->mu);
  for (size_t i = 0; i < num_outputs; ++i) {
    int64_t id = r->next_id++;
    r->buffers[id] = outputs[i];
    out_buf_ids[i] = id;
  }
  return static_cast<int64_t>(num_outputs);
}

// Execute on the single addressable device.  Inputs are buffer handles;
// outputs become new buffer handles written to `out_buf_ids` (which must
// hold at least the executable's output count — query via
// pjrt_runner_num_outputs).  Returns the output count, or -1.
int64_t pjrt_runner_execute(Runner* r, int64_t exec_id,
                            const int64_t* arg_buf_ids, int32_t num_args,
                            int64_t* out_buf_ids) {
  return execute_impl(r, exec_id, arg_buf_ids, num_args, out_buf_ids,
                      /*wait=*/true);
}

// Asynchronous execute: enqueues and returns immediately; output handles
// carry pending definition events.  A later pjrt_runner_get /
// pjrt_runner_await_buffer blocks until compute completes (and surfaces
// any asynchronous failure).  Pairs with pjrt_runner_put_async to
// double-buffer batches: enqueue batch i+1's transfer+execute, then fetch
// batch i's outputs while i+1 runs.
int64_t pjrt_runner_execute_async(Runner* r, int64_t exec_id,
                                  const int64_t* arg_buf_ids,
                                  int32_t num_args, int64_t* out_buf_ids) {
  return execute_impl(r, exec_id, arg_buf_ids, num_args, out_buf_ids,
                      /*wait=*/false);
}

// Debug: describe `buf_id`'s device memory layout into `out` as
// "m2m=[...] tiles=[...]"; returns chars written or -1.
int pjrt_runner_buffer_layout_desc(Runner* r, int64_t buf_id, char* out,
                                   int out_len) {
  PJRT_Buffer* buf;
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->buffers.find(buf_id);
    if (it == r->buffers.end()) {
      set_err(r, "bad buffer handle");
      return -1;
    }
    buf = it->second;
  }
  PJRT_Buffer_GetMemoryLayout_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_GetMemoryLayout_Args_STRUCT_SIZE;
  args.buffer = buf;
  if (take_error(r, r->api->PJRT_Buffer_GetMemoryLayout(&args),
                 "PJRT_Buffer_GetMemoryLayout")) {
    return -1;
  }
  std::string s;
  if (args.layout.type == PJRT_Buffer_MemoryLayout_Type_Tiled) {
    s = "m2m=[";
    for (size_t i = 0; i < args.layout.tiled.minor_to_major_size; ++i) {
      if (i) s += ",";
      s += std::to_string(args.layout.tiled.minor_to_major[i]);
    }
    s += "] tiles=[";
    size_t off = 0;
    for (size_t t = 0; t < args.layout.tiled.num_tiles; ++t) {
      if (t) s += ";";
      for (size_t d = 0; d < args.layout.tiled.tile_dim_sizes[t]; ++d) {
        if (d) s += ",";
        s += std::to_string(args.layout.tiled.tile_dims[off++]);
      }
    }
    s += "]";
  } else {
    s = "strides";
  }
  int n = static_cast<int>(s.size());
  if (n >= out_len) n = out_len - 1;
  std::memcpy(out, s.c_str(), n);
  out[n] = '\0';
  return n;
}

// Dense row-major host layout for `buf`: minor_to_major = [ndim-1 .. 0].
// TPU device buffers are tiled/relaid; fetching with host_layout=nullptr
// would hand back device layout, so every fetch passes this explicitly.
bool row_major_layout(Runner* r, PJRT_Buffer* buf,
                      std::vector<int64_t>* minor_to_major,
                      PJRT_Buffer_MemoryLayout* layout) {
  PJRT_Buffer_Dimensions_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  dargs.buffer = buf;
  if (take_error(r, r->api->PJRT_Buffer_Dimensions(&dargs),
                 "PJRT_Buffer_Dimensions")) {
    return false;
  }
  minor_to_major->resize(dargs.num_dims);
  for (size_t i = 0; i < dargs.num_dims; ++i) {
    (*minor_to_major)[i] = static_cast<int64_t>(dargs.num_dims - 1 - i);
  }
  std::memset(layout, 0, sizeof(*layout));
  layout->struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout->type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout->tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout->tiled.minor_to_major = minor_to_major->data();
  layout->tiled.minor_to_major_size = minor_to_major->size();
  return true;
}

// Size in bytes required to fetch `buf_id` to the host (-1 on error).
int64_t pjrt_runner_buffer_size(Runner* r, int64_t buf_id) {
  PJRT_Buffer* buf;
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->buffers.find(buf_id);
    if (it == r->buffers.end()) {
      set_err(r, "bad buffer handle");
      return -1;
    }
    buf = it->second;
  }
  std::vector<int64_t> m2m;
  PJRT_Buffer_MemoryLayout layout;
  if (!row_major_layout(r, buf, &m2m, &layout)) return -1;
  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = buf;
  args.host_layout = &layout;
  args.dst = nullptr;  // size query
  if (take_error(r, r->api->PJRT_Buffer_ToHostBuffer(&args),
                 "PJRT_Buffer_ToHostBuffer(size)")) {
    return -1;
  }
  return static_cast<int64_t>(args.dst_size);
}

// Synchronously fetch a device buffer into `dst` (dst_size from
// pjrt_runner_buffer_size).  Returns 0, or -1 on error.
int pjrt_runner_get(Runner* r, int64_t buf_id, void* dst, int64_t dst_size) {
  PJRT_Buffer* buf;
  {
    std::lock_guard<std::mutex> lock(r->mu);
    auto it = r->buffers.find(buf_id);
    if (it == r->buffers.end()) {
      set_err(r, "bad buffer handle");
      return -1;
    }
    buf = it->second;
  }
  std::vector<int64_t> m2m;
  PJRT_Buffer_MemoryLayout layout;
  if (!row_major_layout(r, buf, &m2m, &layout)) return -1;
  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = buf;
  args.host_layout = &layout;
  args.dst = dst;
  args.dst_size = static_cast<size_t>(dst_size);
  if (take_error(r, r->api->PJRT_Buffer_ToHostBuffer(&args),
                 "PJRT_Buffer_ToHostBuffer")) {
    return -1;
  }
  return await_event(r, args.event, "device->host copy") ? 0 : -1;
}

void pjrt_runner_destroy(Runner* r) {
  if (!r) return;
  for (auto& kv : r->buffers) {
    PJRT_Buffer_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = kv.second;
    PJRT_Error* err = r->api->PJRT_Buffer_Destroy(&args);
    take_error(r, err, "PJRT_Buffer_Destroy");
  }
  for (auto& kv : r->execs) {
    PJRT_LoadedExecutable_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    args.executable = kv.second;
    PJRT_Error* err = r->api->PJRT_LoadedExecutable_Destroy(&args);
    take_error(r, err, "PJRT_LoadedExecutable_Destroy");
  }
  if (r->client) {
    PJRT_Client_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = r->client;
    PJRT_Error* err = r->api->PJRT_Client_Destroy(&args);
    take_error(r, err, "PJRT_Client_Destroy");
  }
  if (r->dl) dlclose(r->dl);
  delete r;
}

}  // extern "C"
