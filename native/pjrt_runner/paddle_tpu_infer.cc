// libpaddle_tpu_infer — the linkable native inference engine.
//
// Reference analog: paddle/fluid/inference/api/api.cc (the engine behind
// both the C++ and C inference APIs). Here the engine is a PJRT C-API
// host loop over an exported StableHLO artifact; pjrt_runner.cc is the
// thin CLI client of this library and tests/test_native_capi.py links a
// plain-C smoke test against it.

#include "paddle_tpu_infer.h"

#include <dlfcn.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#include "pjrt_c_api.h"
#include "pjrt_util.h"

namespace {

using pjrt_util::DtypeSize;
using pjrt_util::DtypeToPjrt;
using pjrt_util::ParseSection;
using pjrt_util::ReadFile;
using pjrt_util::TensorMeta;

size_t ByteSize(const TensorMeta& m) {
  size_t n = DtypeSize(m.dtype);
  for (int64_t d : m.shape) n *= d;
  return n;
}

void SetErr(char* errbuf, int errlen, const std::string& msg) {
  if (errbuf && errlen > 0) {
    std::snprintf(errbuf, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

}  // namespace

struct PTI_Predictor {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;
  std::vector<TensorMeta> in_meta, out_meta;
  // weights-external artifacts: param buffers staged ONCE at create and
  // passed as leading execute args on every run (manifest "params")
  std::vector<TensorMeta> param_meta;
  std::vector<PJRT_Buffer*> param_bufs;
  std::string err;  // last error (internal)

  bool Check(PJRT_Error* e, const char* what) {
    if (e == nullptr) return true;
    PJRT_Error_Message_Args margs = {};
    margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    margs.extension_start = nullptr;
    margs.error = e;
    api->PJRT_Error_Message(&margs);
    err = std::string(what) + ": " +
          std::string(margs.message, margs.message_size);
    PJRT_Error_Destroy_Args dargs = {};
    dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    dargs.extension_start = nullptr;
    dargs.error = e;
    api->PJRT_Error_Destroy(&dargs);
    return false;
  }

  bool Await(PJRT_Event* event, const char* what) {
    PJRT_Event_Await_Args args = {};
    args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    args.extension_start = nullptr;
    args.event = event;
    if (!Check(api->PJRT_Event_Await(&args), what)) return false;
    PJRT_Event_Destroy_Args d = {};
    d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    d.extension_start = nullptr;
    d.event = event;
    return Check(api->PJRT_Event_Destroy(&d), "event destroy");
  }
};

// one H2D staging path for params and inputs: fills *buf and the
// transfer-done event; p->err carries the failure message
static bool StageHostBuffer(PTI_Predictor* p, const void* data,
                            const TensorMeta& meta, PJRT_Buffer** buf,
                            PJRT_Event** done) {
  PJRT_Buffer_Type t;
  if (!DtypeToPjrt(meta.dtype, &t)) {
    p->err = "unsupported dtype " + meta.dtype;
    return false;
  }
  PJRT_Client_BufferFromHostBuffer_Args hb = {};
  hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  hb.extension_start = nullptr;
  hb.client = p->client;
  hb.data = data;
  hb.type = t;
  hb.dims = meta.shape.data();
  hb.num_dims = meta.shape.size();
  hb.byte_strides = nullptr;
  hb.num_byte_strides = 0;
  hb.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  hb.device = p->device;
  hb.memory = nullptr;
  hb.device_layout = nullptr;
  if (!p->Check(p->api->PJRT_Client_BufferFromHostBuffer(&hb), "h2d"))
    return false;
  *buf = hb.buffer;
  *done = hb.done_with_host_buffer;
  return true;
}

static PTI_Predictor* CreateImpl(const char* plugin_so,
                                 const char* artifact_dir,
                                 const char* const* option_kv,
                                 int num_options, char* errbuf,
                                 int errbuf_len) {
  auto* p = new PTI_Predictor();
  std::string err;
  auto fail = [&](const std::string& m) -> PTI_Predictor* {
    SetErr(errbuf, errbuf_len, m);
    PTI_Destroy(p);
    return nullptr;
  };

  p->dl = dlopen(plugin_so, RTLD_NOW | RTLD_LOCAL);
  if (!p->dl) return fail(std::string("dlopen: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(p->dl, "GetPjrtApi"));
  if (!get_api) return fail("plugin has no GetPjrtApi symbol");
  p->api = get_api();

  PJRT_Plugin_Initialize_Args pi = {};
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  pi.extension_start = nullptr;
  if (!p->Check(p->api->PJRT_Plugin_Initialize(&pi), "plugin init"))
    return fail(p->err);

  std::vector<std::string> keys(num_options), vals(num_options);
  std::vector<PJRT_NamedValue> named;
  std::vector<int64_t> int_store(num_options);
  for (int i = 0; i < num_options; ++i) {
    std::string kv = option_kv[i];
    size_t eq = kv.find('=');
    if (eq == std::string::npos) return fail("bad option " + kv);
    keys[i] = kv.substr(0, eq);
    vals[i] = kv.substr(eq + 1);
    PJRT_NamedValue v;
    v.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    v.extension_start = nullptr;
    v.name = keys[i].c_str();
    v.name_size = keys[i].size();
    char* endp = nullptr;
    long long as_int = std::strtoll(vals[i].c_str(), &endp, 10);
    if (endp && *endp == '\0' && !vals[i].empty()) {
      int_store[i] = as_int;
      v.type = PJRT_NamedValue_kInt64;
      v.int64_value = int_store[i];
      v.value_size = 1;
    } else {
      v.type = PJRT_NamedValue_kString;
      v.string_value = vals[i].c_str();
      v.value_size = vals[i].size();
    }
    named.push_back(v);
  }

  PJRT_Client_Create_Args cc = {};
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.extension_start = nullptr;
  cc.create_options = named.empty() ? nullptr : named.data();
  cc.num_options = named.size();
  cc.kv_get_callback = nullptr;
  cc.kv_get_user_arg = nullptr;
  cc.kv_put_callback = nullptr;
  cc.kv_put_user_arg = nullptr;
  cc.kv_try_get_callback = nullptr;
  cc.kv_try_get_user_arg = nullptr;
  if (!p->Check(p->api->PJRT_Client_Create(&cc), "client create"))
    return fail(p->err);
  p->client = cc.client;

  PJRT_Client_AddressableDevices_Args ad = {};
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.extension_start = nullptr;
  ad.client = p->client;
  if (!p->Check(p->api->PJRT_Client_AddressableDevices(&ad), "devices"))
    return fail(p->err);
  if (ad.num_addressable_devices == 0) return fail("no addressable devices");
  p->device = ad.addressable_devices[0];

  std::string dir(artifact_dir);
  std::string mlir, copts, manifest;
  if (!ReadFile(dir + "/model.mlir", false, &mlir, &err) ||
      !ReadFile(dir + "/compile_options.pb", true, &copts, &err) ||
      !ReadFile(dir + "/manifest.json", false, &manifest, &err))
    return fail(err);
  p->in_meta = ParseSection(manifest, "inputs");
  p->out_meta = ParseSection(manifest, "outputs");
  p->param_meta = ParseSection(manifest, "params");

  PJRT_Program prog;
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.extension_start = nullptr;
  prog.code = mlir.data();
  prog.code_size = mlir.size();
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = sizeof(kFmt) - 1;

  PJRT_Client_Compile_Args comp = {};
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.extension_start = nullptr;
  comp.client = p->client;
  comp.program = &prog;
  comp.compile_options = copts.data();
  comp.compile_options_size = copts.size();
  if (!p->Check(p->api->PJRT_Client_Compile(&comp), "compile"))
    return fail(p->err);
  p->exec = comp.executable;

  // the executable's REAL output count must match the manifest — PJRT
  // fills output_lists[0][i] for every executable output, so a stale
  // manifest would otherwise overflow the buffer array
  PJRT_LoadedExecutable_GetExecutable_Args ge = {};
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.extension_start = nullptr;
  ge.loaded_executable = p->exec;
  if (!p->Check(p->api->PJRT_LoadedExecutable_GetExecutable(&ge),
                "get executable"))
    return fail(p->err);
  PJRT_Executable_NumOutputs_Args no = {};
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.extension_start = nullptr;
  no.executable = ge.executable;
  if (!p->Check(p->api->PJRT_Executable_NumOutputs(&no), "num outputs"))
    return fail(p->err);
  if (no.num_outputs != p->out_meta.size())
    return fail("manifest lists " + std::to_string(p->out_meta.size()) +
                " outputs but the executable produces " +
                std::to_string(no.num_outputs) +
                " — regenerate the artifact");

  // weights-external artifact: stage every param<i>.bin onto the device
  // now; runs then move only inputs/outputs. All transfers are ISSUED
  // first and awaited after — a per-param await would serialize ~200
  // round trips at predictor create
  std::vector<std::string> raws(p->param_meta.size());
  std::vector<PJRT_Event*> dones;
  for (size_t i = 0; i < p->param_meta.size(); ++i) {
    if (!ReadFile(dir + "/param" + std::to_string(i) + ".bin", true,
                  &raws[i], &err))
      return fail(err);
    if (raws[i].size() != ByteSize(p->param_meta[i]))
      return fail("param" + std::to_string(i) + ".bin is " +
                  std::to_string(raws[i].size()) +
                  " bytes, manifest wants " +
                  std::to_string(ByteSize(p->param_meta[i])));
    PJRT_Buffer* buf = nullptr;
    PJRT_Event* done = nullptr;
    if (!StageHostBuffer(p, raws[i].data(), p->param_meta[i], &buf,
                         &done)) {
      for (PJRT_Event* e : dones) p->Await(e, "param h2d done");
      return fail(p->err);
    }
    p->param_bufs.push_back(buf);
    dones.push_back(done);
  }
  for (PJRT_Event* e : dones) {
    if (!p->Await(e, "param h2d done")) return fail(p->err);
  }
  return p;
}

static int RunImpl(PTI_Predictor* p, const void* const* inputs,
                   void* const* outputs, char* errbuf, int errbuf_len);

extern "C" {

// exceptions (e.g. a malformed manifest in ParseSection) must never
// unwind through the C ABI: the contract is NULL/nonzero + errbuf
PTI_Predictor* PTI_Create(const char* plugin_so, const char* artifact_dir,
                          const char* const* option_kv, int num_options,
                          char* errbuf, int errbuf_len) {
  try {
    return CreateImpl(plugin_so, artifact_dir, option_kv, num_options,
                      errbuf, errbuf_len);
  } catch (const std::exception& e) {
    SetErr(errbuf, errbuf_len, std::string("create: ") + e.what());
    return nullptr;
  } catch (...) {
    SetErr(errbuf, errbuf_len, "create: unknown error");
    return nullptr;
  }
}

int PTI_Run(PTI_Predictor* p, const void* const* inputs,
            void* const* outputs, char* errbuf, int errbuf_len) {
  try {
    return RunImpl(p, inputs, outputs, errbuf, errbuf_len);
  } catch (const std::exception& e) {
    SetErr(errbuf, errbuf_len, std::string("run: ") + e.what());
    return 1;
  } catch (...) {
    SetErr(errbuf, errbuf_len, "run: unknown error");
    return 1;
  }
}

int PTI_NumInputs(const PTI_Predictor* p) {
  return static_cast<int>(p->in_meta.size());
}
int PTI_NumOutputs(const PTI_Predictor* p) {
  return static_cast<int>(p->out_meta.size());
}

static int FillShape(const std::vector<TensorMeta>& metas, int i,
                     long long* dims, int max_dims) {
  if (i < 0 || i >= static_cast<int>(metas.size())) return -1;
  const auto& s = metas[i].shape;
  if (static_cast<int>(s.size()) > max_dims) return -1;
  for (size_t k = 0; k < s.size(); ++k) dims[k] = s[k];
  return static_cast<int>(s.size());
}

int PTI_InputShape(const PTI_Predictor* p, int i, long long* dims,
                   int max_dims) {
  return FillShape(p->in_meta, i, dims, max_dims);
}
int PTI_OutputShape(const PTI_Predictor* p, int i, long long* dims,
                    int max_dims) {
  return FillShape(p->out_meta, i, dims, max_dims);
}

const char* PTI_InputDtype(const PTI_Predictor* p, int i) {
  if (i < 0 || i >= static_cast<int>(p->in_meta.size())) return nullptr;
  return p->in_meta[i].dtype.c_str();
}
const char* PTI_OutputDtype(const PTI_Predictor* p, int i) {
  if (i < 0 || i >= static_cast<int>(p->out_meta.size())) return nullptr;
  return p->out_meta[i].dtype.c_str();
}

long long PTI_InputByteSize(const PTI_Predictor* p, int i) {
  if (i < 0 || i >= static_cast<int>(p->in_meta.size())) return -1;
  return static_cast<long long>(ByteSize(p->in_meta[i]));
}
long long PTI_OutputByteSize(const PTI_Predictor* p, int i) {
  if (i < 0 || i >= static_cast<int>(p->out_meta.size())) return -1;
  return static_cast<long long>(ByteSize(p->out_meta[i]));
}

}  // extern "C"

static int RunImpl(PTI_Predictor* p, const void* const* inputs,
                   void* const* outputs, char* errbuf, int errbuf_len) {
  std::vector<PJRT_Buffer*> in_bufs;
  std::vector<PJRT_Buffer*> out_bufs(p->out_meta.size(), nullptr);
  auto destroy_all = [&]() {
    // PTI_Run must be retryable from a long-lived serving process: every
    // buffer created before a failure is released, never leaked
    for (auto* bufs : {&in_bufs, &out_bufs}) {
      for (PJRT_Buffer* b : *bufs) {
        if (!b) continue;
        PJRT_Buffer_Destroy_Args bd = {};
        bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        bd.extension_start = nullptr;
        bd.buffer = b;
        p->Check(p->api->PJRT_Buffer_Destroy(&bd), "buffer destroy");
      }
    }
  };
  auto fail = [&](const std::string& m) {
    destroy_all();
    SetErr(errbuf, errbuf_len, m);
    return 1;
  };
  in_bufs.reserve(p->in_meta.size());
  {
    std::vector<PJRT_Event*> dones;
    for (size_t i = 0; i < p->in_meta.size(); ++i) {
      PJRT_Buffer* buf = nullptr;
      PJRT_Event* done = nullptr;
      if (!StageHostBuffer(p, inputs[i], p->in_meta[i], &buf, &done)) {
        for (PJRT_Event* e : dones) p->Await(e, "h2d done");
        return fail(p->err);
      }
      in_bufs.push_back(buf);
      dones.push_back(done);
    }
    for (PJRT_Event* e : dones) {
      if (!p->Await(e, "h2d done")) return fail(p->err);
    }
  }

  PJRT_ExecuteOptions eo = {};
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  eo.extension_start = nullptr;
  eo.send_callbacks = nullptr;
  eo.recv_callbacks = nullptr;
  eo.num_send_ops = 0;
  eo.num_recv_ops = 0;
  eo.launch_id = 0;
  eo.non_donatable_input_indices = nullptr;
  eo.num_non_donatable_input_indices = 0;
  eo.context = nullptr;

  PJRT_LoadedExecutable_Execute_Args ex = {};
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.extension_start = nullptr;
  ex.executable = p->exec;
  ex.options = &eo;
  // weights-external modules take the resident param buffers first
  std::vector<PJRT_Buffer*> args(p->param_bufs);
  args.insert(args.end(), in_bufs.begin(), in_bufs.end());
  PJRT_Buffer* const* arg_list = args.data();
  ex.argument_lists = &arg_list;
  ex.num_devices = 1;
  ex.num_args = args.size();
  PJRT_Buffer** out_list = out_bufs.data();
  ex.output_lists = &out_list;
  PJRT_Event* done = nullptr;
  ex.device_complete_events = &done;
  ex.execute_device = nullptr;
  if (!p->Check(p->api->PJRT_LoadedExecutable_Execute(&ex), "execute"))
    return fail(p->err);
  if (done && !p->Await(done, "execute done")) return fail(p->err);

  std::string d2h_err;
  for (size_t i = 0; i < out_bufs.size(); ++i) {
    if (d2h_err.empty()) {
      PJRT_Buffer_ToHostBuffer_Args th = {};
      th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      th.extension_start = nullptr;
      th.src = out_bufs[i];
      th.host_layout = nullptr;
      th.dst = outputs[i];
      th.dst_size = ByteSize(p->out_meta[i]);
      if (!p->Check(p->api->PJRT_Buffer_ToHostBuffer(&th), "d2h") ||
          !p->Await(th.event, "d2h done"))
        d2h_err = p->err;
    }
  }
  destroy_all();
  if (!d2h_err.empty()) {
    SetErr(errbuf, errbuf_len, d2h_err);
    return 1;
  }
  return 0;
}

extern "C" {

void PTI_Destroy(PTI_Predictor* p) {
  if (!p) return;
  if (p->api) {
    for (PJRT_Buffer* b : p->param_bufs) {
      PJRT_Buffer_Destroy_Args bd = {};
      bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      bd.extension_start = nullptr;
      bd.buffer = b;
      p->api->PJRT_Buffer_Destroy(&bd);
    }
    if (p->exec) {
      PJRT_LoadedExecutable_Destroy_Args d = {};
      d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      d.extension_start = nullptr;
      d.executable = p->exec;
      p->api->PJRT_LoadedExecutable_Destroy(&d);
    }
    if (p->client) {
      PJRT_Client_Destroy_Args d = {};
      d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      d.extension_start = nullptr;
      d.client = p->client;
      p->api->PJRT_Client_Destroy(&d);
    }
  }
  // the plugin .so stays loaded (unloading PJRT plugins is unsafe)
  delete p;
}

}  // extern "C"
