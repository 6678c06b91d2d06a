// PJRT C-API bridge for go_libp2p_pubsub_tpu.
//
// The survey (§2, BUILD-NEW) calls for a native bridge that can invoke
// compiled XLA programs from a non-Python host runtime — the TPU-native
// analogue of embedding the simulator in a Go-facing API the way the
// reference embeds its router in a libp2p host. This is that bridge: a
// thin C ABI over the PJRT C API (the stable plugin ABI every XLA backend
// exports — libtpu, CPU, GPU plugins alike). A host program dlopens a
// plugin, compiles a StableHLO module (e.g. produced by jax.export from
// the vectorized router step), and executes it against host buffers with
// zero Python in the loop.
//
// The ctypes counterpart lives in go_libp2p_pubsub_tpu/native/pjrt.py;
// the same C ABI is directly consumable from Go via cgo.
//
// Single-device by design (the simulator's multi-chip path is driven by
// jit/GSPMD inside one program); errors are returned as strings through
// caller-provided buffers.

#include <cstdint>
#include <cstring>
#include <cstdlib>

#include <dlfcn.h>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Bridge {
  void *dl = nullptr;
  const PJRT_Api *api = nullptr;
};

void set_err(char *err, size_t errlen, const char *msg, size_t msglen = 0) {
  if (!err || errlen == 0) return;
  if (msglen == 0) msglen = strlen(msg);
  size_t n = msglen < errlen - 1 ? msglen : errlen - 1;
  memcpy(err, msg, n);
  err[n] = '\0';
}

// Returns true on error (and fills err).
bool check(const Bridge *b, PJRT_Error *e, char *err, size_t errlen) {
  if (!e) return false;
  PJRT_Error_Message_Args m;
  memset(&m, 0, sizeof m);
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = e;
  b->api->PJRT_Error_Message(&m);
  set_err(err, errlen, m.message, m.message_size);
  PJRT_Error_Destroy_Args d;
  memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = e;
  b->api->PJRT_Error_Destroy(&d);
  return true;
}

bool await_event(const Bridge *b, PJRT_Event *ev, char *err, size_t errlen) {
  if (!ev) return false;
  PJRT_Event_Await_Args aw;
  memset(&aw, 0, sizeof aw);
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  PJRT_Error *e = b->api->PJRT_Event_Await(&aw);
  bool bad = check(b, e, err, errlen);
  PJRT_Event_Destroy_Args d;
  memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  b->api->PJRT_Event_Destroy(&d);
  return bad;
}

}  // namespace

extern "C" {

// dlopen a PJRT plugin (libtpu.so / a CPU plugin),
// resolve GetPjrtApi and run PJRT_Plugin_Initialize. NULL + err on failure.
void *pjx_load(const char *plugin_path, char *err, size_t errlen) {
  void *dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) {
    set_err(err, errlen, dlerror());
    return nullptr;
  }
  using GetApiFn = const PJRT_Api *(*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errlen, "GetPjrtApi symbol not found");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api *api = get_api();
  if (!api) {
    set_err(err, errlen, "GetPjrtApi returned NULL");
    dlclose(dl);
    return nullptr;
  }
  Bridge *b = new Bridge{dl, api};
  PJRT_Plugin_Initialize_Args init;
  memset(&init, 0, sizeof init);
  init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (check(b, api->PJRT_Plugin_Initialize(&init), err, errlen)) {
    dlclose(dl);
    delete b;
    return nullptr;
  }
  return b;
}

void pjx_unload(void *h) {
  Bridge *b = static_cast<Bridge *>(h);
  if (!b) return;
  if (b->dl) dlclose(b->dl);
  delete b;
}

void pjx_api_version(void *h, int *major, int *minor) {
  Bridge *b = static_cast<Bridge *>(h);
  *major = b->api->pjrt_api_version.major_version;
  *minor = b->api->pjrt_api_version.minor_version;
}

// Create a client with `nopts` NamedValue create options. Per option i:
// types[i] 0 -> string (string_values[i]), 1 -> int64 (int_values[i]),
// 2 -> bool (int_values[i] != 0), 3 -> float (reinterpreted from
// int_values[i]'s low 32 bits). Plugins are configured this way (libtpu
// needs none).
void *pjx_client_create(void *h, const char **names, const int *types,
                        const char **string_values, const int64_t *int_values,
                        size_t nopts, char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_NamedValue *opts = nullptr;
  if (nopts > 0) {
    opts = static_cast<PJRT_NamedValue *>(calloc(nopts, sizeof(PJRT_NamedValue)));
    for (size_t i = 0; i < nopts; i++) {
      opts[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
      opts[i].name = names[i];
      opts[i].name_size = strlen(names[i]);
      switch (types[i]) {
        case 0:
          opts[i].type = PJRT_NamedValue_kString;
          opts[i].string_value = string_values[i];
          opts[i].value_size = strlen(string_values[i]);
          break;
        case 1:
          opts[i].type = PJRT_NamedValue_kInt64;
          opts[i].int64_value = int_values[i];
          opts[i].value_size = 1;
          break;
        case 2:
          opts[i].type = PJRT_NamedValue_kBool;
          opts[i].bool_value = int_values[i] != 0;
          opts[i].value_size = 1;
          break;
        default: {
          opts[i].type = PJRT_NamedValue_kFloat;
          uint32_t bits = static_cast<uint32_t>(int_values[i]);
          float f;
          memcpy(&f, &bits, sizeof f);
          opts[i].float_value = f;
          opts[i].value_size = 1;
          break;
        }
      }
    }
  }
  PJRT_Client_Create_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  a.create_options = opts;
  a.num_options = nopts;
  PJRT_Error *e = b->api->PJRT_Client_Create(&a);
  free(opts);
  if (check(b, e, err, errlen)) return nullptr;
  return a.client;
}

void pjx_client_destroy(void *h, void *client) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Client_Destroy_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  a.client = static_cast<PJRT_Client *>(client);
  b->api->PJRT_Client_Destroy(&a);
}

// Platform name into buf; returns name length or -1.
long pjx_platform_name(void *h, void *client, char *buf, size_t buflen,
                       char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Client_PlatformName_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  a.client = static_cast<PJRT_Client *>(client);
  if (check(b, b->api->PJRT_Client_PlatformName(&a), err, errlen)) return -1;
  size_t n = a.platform_name_size < buflen - 1 ? a.platform_name_size : buflen - 1;
  memcpy(buf, a.platform_name, n);
  buf[n] = '\0';
  return static_cast<long>(a.platform_name_size);
}

// Device count (addressable != 0 -> addressable devices only); -1 on error.
long pjx_device_count(void *h, void *client, int addressable,
                      char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  if (addressable) {
    PJRT_Client_AddressableDevices_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    a.client = static_cast<PJRT_Client *>(client);
    if (check(b, b->api->PJRT_Client_AddressableDevices(&a), err, errlen))
      return -1;
    return static_cast<long>(a.num_addressable_devices);
  }
  PJRT_Client_Devices_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_Devices_Args_STRUCT_SIZE;
  a.client = static_cast<PJRT_Client *>(client);
  if (check(b, b->api->PJRT_Client_Devices(&a), err, errlen)) return -1;
  return static_cast<long>(a.num_devices);
}

// Compile `code` (format "mlir" for StableHLO bytecode/text, or "hlo").
// `options` is a serialized xla CompileOptionsProto.
void *pjx_compile(void *h, void *client, const char *code, size_t code_size,
                  const char *format, const char *options, size_t options_size,
                  char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Program prog;
  memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char *>(code);
  prog.code_size = code_size;
  prog.format = format;
  prog.format_size = strlen(format);
  PJRT_Client_Compile_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  a.client = static_cast<PJRT_Client *>(client);
  a.program = &prog;
  a.compile_options = options;
  a.compile_options_size = options_size;
  if (check(b, b->api->PJRT_Client_Compile(&a), err, errlen)) return nullptr;
  return a.executable;
}

void pjx_executable_destroy(void *h, void *exe) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_LoadedExecutable_Destroy_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  a.executable = static_cast<PJRT_LoadedExecutable *>(exe);
  b->api->PJRT_LoadedExecutable_Destroy(&a);
}

// Number of outputs per device of a loaded executable; -1 on error.
long pjx_num_outputs(void *h, void *exe, char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_LoadedExecutable_GetExecutable_Args g;
  memset(&g, 0, sizeof g);
  g.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  g.loaded_executable = static_cast<PJRT_LoadedExecutable *>(exe);
  if (check(b, b->api->PJRT_LoadedExecutable_GetExecutable(&g), err, errlen))
    return -1;
  PJRT_Executable_NumOutputs_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  a.executable = g.executable;
  if (check(b, b->api->PJRT_Executable_NumOutputs(&a), err, errlen)) return -1;
  return static_cast<long>(a.num_outputs);
}

// Copy a dense major-to-minor host array to the first addressable device.
// `dtype` is a PJRT_Buffer_Type value. NULL + err on failure.
void *pjx_buffer_from_host(void *h, void *client, const void *data, int dtype,
                           const int64_t *dims, size_t ndims,
                           char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Client_AddressableDevices_Args da;
  memset(&da, 0, sizeof da);
  da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  da.client = static_cast<PJRT_Client *>(client);
  if (check(b, b->api->PJRT_Client_AddressableDevices(&da), err, errlen))
    return nullptr;
  if (da.num_addressable_devices == 0) {
    set_err(err, errlen, "no addressable devices");
    return nullptr;
  }
  PJRT_Client_BufferFromHostBuffer_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  a.client = static_cast<PJRT_Client *>(client);
  a.data = data;
  a.type = static_cast<PJRT_Buffer_Type>(dtype);
  a.dims = dims;
  a.num_dims = ndims;
  a.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  a.device = da.addressable_devices[0];
  if (check(b, b->api->PJRT_Client_BufferFromHostBuffer(&a), err, errlen))
    return nullptr;
  if (await_event(b, a.done_with_host_buffer, err, errlen)) {
    return nullptr;
  }
  return a.buffer;
}

void pjx_buffer_destroy(void *h, void *buf) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Buffer_Destroy_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  a.buffer = static_cast<PJRT_Buffer *>(buf);
  b->api->PJRT_Buffer_Destroy(&a);
}

// Buffer shape: fills dims (capacity max_dims), returns ndims; -1 on error.
long pjx_buffer_dims(void *h, void *buf, int64_t *dims, size_t max_dims,
                     char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Buffer_Dimensions_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  a.buffer = static_cast<PJRT_Buffer *>(buf);
  if (check(b, b->api->PJRT_Buffer_Dimensions(&a), err, errlen)) return -1;
  for (size_t i = 0; i < a.num_dims && i < max_dims; i++) dims[i] = a.dims[i];
  return static_cast<long>(a.num_dims);
}

// PJRT_Buffer_Type of the buffer; -1 on error.
long pjx_buffer_dtype(void *h, void *buf, char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  PJRT_Buffer_ElementType_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  a.buffer = static_cast<PJRT_Buffer *>(buf);
  if (check(b, b->api->PJRT_Buffer_ElementType(&a), err, errlen)) return -1;
  return static_cast<long>(a.type);
}

// Blocking device->host copy. If dst is NULL, returns required byte size.
// `row_major` != 0 requests a dense row-major host layout (minor-to-major
// = reversed dims) — device buffers are typically tiled on TPU, so
// callers reading into numpy must pass it. Tiled form, not Strides:
// plugins follow jaxlib's ToLiteral path, which only passes Tiled.
long pjx_buffer_to_host(void *h, void *buf, void *dst, size_t dst_size,
                        long row_major, char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  int64_t m2m[16];
  PJRT_Buffer_MemoryLayout layout;
  memset(&layout, 0, sizeof layout);
  PJRT_Buffer_ToHostBuffer_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  a.src = static_cast<PJRT_Buffer *>(buf);
  a.dst = dst;
  a.dst_size = dst_size;
  if (row_major > 0 && dst != nullptr) {
    PJRT_Buffer_Dimensions_Args da;
    memset(&da, 0, sizeof da);
    da.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    da.buffer = static_cast<PJRT_Buffer *>(buf);
    if (check(b, b->api->PJRT_Buffer_Dimensions(&da), err, errlen)) return -1;
    if (da.num_dims <= 16) {
      for (size_t i = 0; i < da.num_dims; i++)
        m2m[i] = static_cast<int64_t>(da.num_dims - 1 - i);
      layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
      layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
      layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
      layout.tiled.minor_to_major = m2m;
      layout.tiled.minor_to_major_size = da.num_dims;
      a.host_layout = &layout;
    }
  }
  if (check(b, b->api->PJRT_Buffer_ToHostBuffer(&a), err, errlen)) return -1;
  if (dst == nullptr) return static_cast<long>(a.dst_size);
  if (await_event(b, a.event, err, errlen)) return -1;
  return static_cast<long>(a.dst_size);
}

// Single-device synchronous execute: inputs[nin] -> outputs[max_out].
// Returns the number of outputs, or -1 on error.
long pjx_execute(void *h, void *exe, void *const *inputs, size_t nin,
                 void **outputs, size_t max_out, char *err, size_t errlen) {
  Bridge *b = static_cast<Bridge *>(h);
  long nout = pjx_num_outputs(h, exe, err, errlen);
  if (nout < 0) return -1;
  if (static_cast<size_t>(nout) > max_out) {
    set_err(err, errlen, "output capacity too small");
    return -1;
  }

  PJRT_ExecuteOptions opts;
  memset(&opts, 0, sizeof opts);
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer **argv = reinterpret_cast<PJRT_Buffer **>(
      const_cast<void **>(inputs));
  PJRT_Buffer *const *arg_list[1] = {argv};
  PJRT_Buffer **out_inner =
      static_cast<PJRT_Buffer **>(calloc(nout > 0 ? nout : 1, sizeof(PJRT_Buffer *)));
  PJRT_Buffer **out_list[1] = {out_inner};
  PJRT_Event *done[1] = {nullptr};

  PJRT_LoadedExecutable_Execute_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  a.executable = static_cast<PJRT_LoadedExecutable *>(exe);
  a.options = &opts;
  a.argument_lists = arg_list;
  a.num_devices = 1;
  a.num_args = nin;
  a.output_lists = out_list;
  a.device_complete_events = done;
  if (check(b, b->api->PJRT_LoadedExecutable_Execute(&a), err, errlen)) {
    free(out_inner);
    return -1;
  }
  if (await_event(b, done[0], err, errlen)) {
    free(out_inner);
    return -1;
  }
  for (long i = 0; i < nout; i++) outputs[i] = out_inner[i];
  free(out_inner);
  return nout;
}

}  // extern "C"
