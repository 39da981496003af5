// Standalone C++ TRAINING loop over the PJRT C API.
//
// The reference trains without Python through its C++ Executor
// (paddle/fluid/train/demo/demo_trainer.cc: load program desc, run the
// startup program, loop Run() over the main program). The TPU-native
// equivalent: the framework exports the WHOLE train step — forward,
// backward, optimizer update, PRNG-state advance — as one StableHLO
// computation with the parameter carry donated in/out
// (inference.export_train_step), and this host loop keeps the carry
// buffers resident on device between steps: no h2d/d2h inside the loop
// except the per-step loss scalar.
//
//   pjrt_trainer <plugin.so> <artifact_dir> <steps> [-o key=value ...]
//
// Inputs come from <artifact_dir>/in<i>.bin (params + constants + one
// batch + PRNG key, as exported); per-step losses are printed and written
// to <artifact_dir>/losses.json; final carry tensors to
// <artifact_dir>/final<j>.bin.
//
// Build:  native/pjrt_runner/build.sh  (builds both runner and trainer)

#include <dlfcn.h>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "pjrt_trainer: %s\n", msg.c_str());
  std::exit(1);
}

std::string ReadFile(const std::string& path, bool binary = true) {
  std::ifstream f(path, binary ? std::ios::binary : std::ios::in);
  if (!f) Die("cannot open " + path);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

const PJRT_Api* g_api = nullptr;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs = {};
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.extension_start = nullptr;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs = {};
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.extension_start = nullptr;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  Die(std::string(what) + ": " + msg);
}

void Await(PJRT_Event* event, const char* what) {
  PJRT_Event_Await_Args args = {};
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.extension_start = nullptr;
  args.event = event;
  Check(g_api->PJRT_Event_Await(&args), what);
  PJRT_Event_Destroy_Args d = {};
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.event = event;
  Check(g_api->PJRT_Event_Destroy(&d), "event destroy");
}

// ---- manifest parsing (flat, trusted artifact) -----------------------------

struct TensorMeta {
  std::vector<int64_t> shape;
  std::string dtype;
};

std::vector<TensorMeta> ParseSection(const std::string& js,
                                     const std::string& section) {
  std::vector<TensorMeta> out;
  size_t sec = js.find("\"" + section + "\"");
  if (sec == std::string::npos) return out;
  size_t open = js.find("[", sec);
  int depth = 0;
  size_t close = open;
  for (size_t i = open; i < js.size(); ++i) {
    if (js[i] == '[') depth++;
    if (js[i] == ']' && --depth == 0) {
      close = i;
      break;
    }
  }
  std::string body = js.substr(open, close - open + 1);
  size_t pos = 0;
  while (true) {
    size_t sh = body.find("\"shape\"", pos);
    if (sh == std::string::npos) break;
    size_t lb = body.find("[", sh);
    size_t rb = body.find("]", lb);
    TensorMeta m;
    std::string nums = body.substr(lb + 1, rb - lb - 1);
    std::stringstream ns(nums);
    std::string tok;
    while (std::getline(ns, tok, ','))
      if (!tok.empty()) m.shape.push_back(std::stoll(tok));
    size_t dt = body.find("\"dtype\"", rb);
    size_t q1 = body.find('"', body.find(':', dt));
    size_t q2 = body.find('"', q1 + 1);
    m.dtype = body.substr(q1 + 1, q2 - q1 - 1);
    out.push_back(m);
    pos = q2;
  }
  return out;
}

// "carry": [[out, in], ...] — pairs of ints
std::vector<std::pair<int, int>> ParsePairs(const std::string& js,
                                            const std::string& key) {
  std::vector<std::pair<int, int>> out;
  size_t sec = js.find("\"" + key + "\"");
  if (sec == std::string::npos) return out;
  size_t open = js.find("[", sec);
  int depth = 0;
  size_t close = open;
  for (size_t i = open; i < js.size(); ++i) {
    if (js[i] == '[') depth++;
    if (js[i] == ']' && --depth == 0) {
      close = i;
      break;
    }
  }
  std::string body = js.substr(open + 1, close - open - 1);
  size_t pos = 0;
  while (true) {
    size_t lb = body.find('[', pos);
    if (lb == std::string::npos) break;
    size_t rb = body.find(']', lb);
    std::string nums = body.substr(lb + 1, rb - lb - 1);
    size_t comma = nums.find(',');
    out.emplace_back(std::stoi(nums.substr(0, comma)),
                     std::stoi(nums.substr(comma + 1)));
    pos = rb + 1;
  }
  return out;
}

// "loss_outputs": [i, ...]
std::vector<int> ParseInts(const std::string& js, const std::string& key) {
  std::vector<int> out;
  size_t sec = js.find("\"" + key + "\"");
  if (sec == std::string::npos) return out;
  size_t open = js.find("[", sec);
  size_t close = js.find("]", open);
  std::string nums = js.substr(open + 1, close - open - 1);
  std::stringstream ns(nums);
  std::string tok;
  while (std::getline(ns, tok, ','))
    if (!tok.empty() && tok.find_first_not_of(" \n\t") != std::string::npos)
      out.push_back(std::stoi(tok));
  return out;
}

PJRT_Buffer_Type DtypeToPjrt(const std::string& d) {
  if (d == "float32") return PJRT_Buffer_Type_F32;
  if (d == "float64") return PJRT_Buffer_Type_F64;
  if (d == "bfloat16") return PJRT_Buffer_Type_BF16;
  if (d == "float16") return PJRT_Buffer_Type_F16;
  if (d == "int64") return PJRT_Buffer_Type_S64;
  if (d == "int32") return PJRT_Buffer_Type_S32;
  if (d == "uint32") return PJRT_Buffer_Type_U32;
  if (d == "int8") return PJRT_Buffer_Type_S8;
  if (d == "uint8") return PJRT_Buffer_Type_U8;
  if (d == "bool") return PJRT_Buffer_Type_PRED;
  Die("unsupported dtype " + d);
}

size_t DtypeSize(const std::string& d) {
  if (d == "float64" || d == "int64") return 8;
  if (d == "float32" || d == "int32" || d == "uint32") return 4;
  if (d == "bfloat16" || d == "float16") return 2;
  return 1;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 4) {
    std::fprintf(stderr,
                 "usage: %s <plugin.so> <artifact_dir> <steps> "
                 "[-o key=value ...]\n",
                 argv[0]);
    return 2;
  }
  const std::string plugin = argv[1];
  const std::string dir = argv[2];
  const int steps = std::atoi(argv[3]);
  if (steps <= 0) Die("steps must be positive");
  std::vector<std::pair<std::string, std::string>> opts;
  for (int i = 4; i < argc; ++i) {
    if (std::strcmp(argv[i], "-o") == 0 && i + 1 < argc) {
      std::string kv = argv[++i];
      size_t eq = kv.find('=');
      if (eq == std::string::npos) Die("bad -o " + kv);
      opts.emplace_back(kv.substr(0, eq), kv.substr(eq + 1));
    }
  }

  // ---- plugin + client -----------------------------------------------------
  void* handle = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!handle) Die(std::string("dlopen: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(handle, "GetPjrtApi"));
  if (!get_api) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();

  PJRT_Plugin_Initialize_Args pi = {};
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  pi.extension_start = nullptr;
  Check(g_api->PJRT_Plugin_Initialize(&pi), "plugin init");

  std::vector<PJRT_NamedValue> named;
  std::vector<int64_t> int_store(opts.size());
  for (size_t i = 0; i < opts.size(); ++i) {
    PJRT_NamedValue v;
    v.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    v.extension_start = nullptr;
    v.name = opts[i].first.c_str();
    v.name_size = opts[i].first.size();
    const std::string& val = opts[i].second;
    char* endp = nullptr;
    long long as_int = std::strtoll(val.c_str(), &endp, 10);
    if (endp && *endp == '\0' && !val.empty()) {
      int_store[i] = as_int;
      v.type = PJRT_NamedValue_kInt64;
      v.int64_value = int_store[i];
      v.value_size = 1;
    } else {
      v.type = PJRT_NamedValue_kString;
      v.string_value = val.c_str();
      v.value_size = val.size();
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
  Check(g_api->PJRT_Client_Create(&cc), "client create");
  PJRT_Client* client = cc.client;

  PJRT_Client_AddressableDevices_Args ad = {};
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.extension_start = nullptr;
  ad.client = client;
  Check(g_api->PJRT_Client_AddressableDevices(&ad), "devices");
  if (ad.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* device = ad.addressable_devices[0];

  // ---- compile -------------------------------------------------------------
  std::string mlir = ReadFile(dir + "/model.mlir", /*binary=*/false);
  std::string copts = ReadFile(dir + "/compile_options.pb");
  std::string manifest = ReadFile(dir + "/manifest.json", false);
  auto in_meta = ParseSection(manifest, "inputs");
  auto out_meta = ParseSection(manifest, "outputs");
  auto carry = ParsePairs(manifest, "carry");
  auto loss_idx = ParseInts(manifest, "loss_outputs");
  if (in_meta.empty() || out_meta.empty() || carry.empty())
    Die("manifest missing inputs/outputs/carry — export with "
        "inference.export_train_step");

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
  comp.client = client;
  comp.program = &prog;
  comp.compile_options = copts.data();
  comp.compile_options_size = copts.size();
  Check(g_api->PJRT_Client_Compile(&comp), "compile");
  PJRT_LoadedExecutable* exec = comp.executable;
  std::printf("compiled %zu-byte train step, %d steps\n", mlir.size(),
              steps);

  // ---- stage initial inputs ------------------------------------------------
  std::vector<PJRT_Buffer*> in_bufs(in_meta.size());
  std::vector<std::string> raw(in_meta.size());
  for (size_t i = 0; i < in_meta.size(); ++i) {
    raw[i] = ReadFile(dir + "/in" + std::to_string(i) + ".bin");
    size_t want = DtypeSize(in_meta[i].dtype);
    for (int64_t d : in_meta[i].shape) want *= d;
    if (raw[i].size() != want)
      Die("in" + std::to_string(i) + " is " +
          std::to_string(raw[i].size()) + " bytes, manifest wants " +
          std::to_string(want));
    PJRT_Client_BufferFromHostBuffer_Args hb = {};
    hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    hb.extension_start = nullptr;
    hb.client = client;
    hb.data = raw[i].data();
    hb.type = DtypeToPjrt(in_meta[i].dtype);
    hb.dims = in_meta[i].shape.data();
    hb.num_dims = in_meta[i].shape.size();
    hb.byte_strides = nullptr;
    hb.num_byte_strides = 0;
    hb.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    hb.device = device;
    hb.memory = nullptr;
    hb.device_layout = nullptr;
    Check(g_api->PJRT_Client_BufferFromHostBuffer(&hb), "h2d");
    Await(hb.done_with_host_buffer, "h2d done");
    in_bufs[i] = hb.buffer;
  }

  // ---- the training loop: carry buffers stay on device ---------------------
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

  std::vector<double> losses;
  std::vector<PJRT_Buffer*> out_bufs(out_meta.size());
  for (int step = 0; step < steps; ++step) {
    PJRT_LoadedExecutable_Execute_Args ex = {};
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.extension_start = nullptr;
    ex.executable = exec;
    ex.options = &eo;
    PJRT_Buffer* const* arg_list = in_bufs.data();
    ex.argument_lists = &arg_list;
    ex.num_devices = 1;
    ex.num_args = in_bufs.size();
    PJRT_Buffer** out_list = out_bufs.data();
    ex.output_lists = &out_list;
    PJRT_Event* done = nullptr;
    ex.device_complete_events = &done;
    ex.execute_device = nullptr;
    Check(g_api->PJRT_LoadedExecutable_Execute(&ex), "execute");
    if (done) Await(done, "execute done");

    // per-step loss scalar(s) d2h
    for (int li : loss_idx) {
      size_t bytes = DtypeSize(out_meta[li].dtype);
      for (int64_t d : out_meta[li].shape) bytes *= d;
      std::string host(bytes, '\0');
      PJRT_Buffer_ToHostBuffer_Args th = {};
      th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      th.extension_start = nullptr;
      th.src = out_bufs[li];
      th.host_layout = nullptr;
      th.dst = host.data();
      th.dst_size = bytes;
      Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "loss d2h");
      Await(th.event, "loss d2h done");
      double v;
      const std::string& dt = out_meta[li].dtype;
      if (dt == "float32") {
        v = *reinterpret_cast<const float*>(host.data());
      } else if (dt == "float64") {
        v = *reinterpret_cast<const double*>(host.data());
      } else {
        Die("loss output dtype " + dt + " not supported by the trainer "
            "(fetch a float32/float64 loss)");
      }
      losses.push_back(v);
      std::printf("step %d loss %.9g\n", step, v);
    }

    // next step: carried outputs become inputs (device-resident); the
    // donated previous carry buffers were consumed by the execute
    if (step + 1 < steps) {
      std::vector<PJRT_Buffer*> next = in_bufs;
      for (auto& [out_j, in_i] : carry) next[in_i] = out_bufs[out_j];
      // non-carried outputs of this step are dead: free them
      std::vector<bool> kept(out_meta.size(), false);
      for (auto& [out_j, in_i] : carry) kept[out_j] = true;
      for (size_t j = 0; j < out_bufs.size(); ++j) {
        if (!kept[j]) {
          PJRT_Buffer_Destroy_Args bd = {};
          bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
          bd.extension_start = nullptr;
          bd.buffer = out_bufs[j];
          Check(g_api->PJRT_Buffer_Destroy(&bd), "buffer destroy");
        }
      }
      in_bufs = next;
    }
  }

  // ---- final carry tensors d2h ---------------------------------------------
  for (size_t k = 0; k < carry.size(); ++k) {
    int j = carry[k].first;
    size_t bytes = DtypeSize(out_meta[j].dtype);
    for (int64_t d : out_meta[j].shape) bytes *= d;
    std::string host(bytes, '\0');
    PJRT_Buffer_ToHostBuffer_Args th = {};
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.extension_start = nullptr;
    th.src = out_bufs[j];
    th.host_layout = nullptr;
    th.dst = host.data();
    th.dst_size = bytes;
    Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "final d2h");
    Await(th.event, "final d2h done");
    std::ofstream of(dir + "/final" + std::to_string(j) + ".bin",
                     std::ios::binary);
    of.write(host.data(), host.size());
  }

  std::ofstream lf(dir + "/losses.json");
  lf.precision(17);  // round-trip exact for f32-derived doubles
  lf << "[";
  for (size_t i = 0; i < losses.size(); ++i)
    lf << (i ? ", " : "") << losses[i];
  lf << "]\n";
  std::printf("OK: %zu losses -> %s/losses.json\n", losses.size(),
              dir.c_str());
  return 0;
}
