// Native serving predictor over the PJRT C API.
//
// TPU-native replacement for the reference's C++ inference stack
// (reference: paddle/fluid/inference/api/analysis_predictor.h:95
// `AnalysisPredictor` — loads a saved program, runs an analysis/pass
// pipeline, executes via NaiveExecutor; and the C++ jit Layer runtime,
// paddle/fluid/jit/layer.h). On this stack the "analysis passes" are
// XLA: the artifact is StableHLO bytecode exported by paddle_tpu.jit.save,
// and the executor is any PJRT plugin (libtpu / CPU) —
// compile once at load, then execute per request with zero Python.
//
// Artifact layout (written by paddle_tpu/jit/__init__.py save()):
//   program.mlir.bc      raw StableHLO module bytecode ("mlir" format)
//   params.pbin          "PTP1" binary: flattened (params, buffers) in
//                        the exported main's leading-argument order
//   compile_options.pb   serialized xla CompileOptionsProto
//
// C ABI (ctypes from paddle_tpu/inference/__init__.py, or standalone
// main in predictor_main.cc):
//   ptpred_create(plugin_path, options, model_dir, err, errlen) -> handle
//   ptpred_num_inputs/num_outputs(handle)
//   ptpred_run(handle, in_ptrs, in_dtypes, in_ndims, in_dims, n_inputs)
//   ptpred_out_ndim/out_dim/out_dtype/out_data(handle, i)
//   ptpred_destroy(handle)
//
// Concurrency (ref: the reference serves AnalysisPredictor behind
// multi-threaded servers — analysis_predictor.h:95 requires one
// predictor clone per thread; here one predictor serves all threads):
// PJRT_LoadedExecutable_Execute is re-entrant and the predictor's
// state (client, executable, resident param buffers) is read-only
// after create, so concurrent requests need only per-request output
// storage. The ptpred_run2 / ptres_* family returns an owned result
// handle per call and is fully thread-safe; the legacy ptpred_run /
// ptpred_out_* family stores results on the predictor and serializes
// that store behind a mutex (reads remain caller-synchronized).
//   ptpred_run2(handle, ins..., err, errlen) -> result handle | NULL
//   ptres_num_outputs/ndim/dim/dtype/data/nbytes(result, ...)
//   ptres_destroy(result)
//
// `options` parameterizes PJRT_Client_Create as "key=i:42;key=s:text".

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <memory>
#include <mutex>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct ErrOut {
  char* buf;
  size_t len;
  void set(const std::string& m) {
    if (buf && len) {
      std::snprintf(buf, len, "%s", m.c_str());
    }
  }
};

std::string PjrtErrMessage(const PJRT_Api* api, PJRT_Error* err) {
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return msg;
}

#define RET_IF_ERR(api, expr, eout, retval)                       \
  do {                                                            \
    PJRT_Error* _e = (expr);                                      \
    if (_e) {                                                     \
      (eout).set(PjrtErrMessage((api), _e));                      \
      return retval;                                              \
    }                                                             \
  } while (0)

bool ReadFile(const std::string& path, std::string* out) {
  std::ifstream f(path, std::ios::binary);
  if (!f) return false;
  std::ostringstream ss;
  ss << f.rdbuf();
  *out = ss.str();
  return true;
}

// dtype codes shared with jit/__init__.py _DTYPE_CODES
PJRT_Buffer_Type DtypeCodeToPjrt(uint32_t code) {
  switch (code) {
    case 0: return PJRT_Buffer_Type_F32;
    case 1: return PJRT_Buffer_Type_F64;
    case 2: return PJRT_Buffer_Type_S32;
    case 3: return PJRT_Buffer_Type_S64;
    case 4: return PJRT_Buffer_Type_BF16;
    case 5: return PJRT_Buffer_Type_F16;
    case 6: return PJRT_Buffer_Type_U8;
    case 7: return PJRT_Buffer_Type_S8;
    case 8: return PJRT_Buffer_Type_PRED;
    case 9: return PJRT_Buffer_Type_U32;
    case 10: return PJRT_Buffer_Type_U64;
    case 11: return PJRT_Buffer_Type_S16;
    case 12: return PJRT_Buffer_Type_U16;
    default: return PJRT_Buffer_Type_INVALID;
  }
}

uint32_t PjrtToDtypeCode(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return 0;
    case PJRT_Buffer_Type_F64: return 1;
    case PJRT_Buffer_Type_S32: return 2;
    case PJRT_Buffer_Type_S64: return 3;
    case PJRT_Buffer_Type_BF16: return 4;
    case PJRT_Buffer_Type_F16: return 5;
    case PJRT_Buffer_Type_U8: return 6;
    case PJRT_Buffer_Type_S8: return 7;
    case PJRT_Buffer_Type_PRED: return 8;
    case PJRT_Buffer_Type_U32: return 9;
    case PJRT_Buffer_Type_U64: return 10;
    case PJRT_Buffer_Type_S16: return 11;
    case PJRT_Buffer_Type_U16: return 12;
    default: return 0xffffffffu;
  }
}

struct HostArray {
  uint32_t dtype_code = 0;
  std::vector<int64_t> dims;
  std::string data;
};

// Parse "k=i:1;k2=s:text" into PJRT named values. Strings referenced by
// the returned PJRT_NamedValue entries are owned by `storage`.
std::vector<PJRT_NamedValue> ParseOptions(
    const std::string& spec, std::vector<std::string>* storage) {
  std::vector<PJRT_NamedValue> out;
  std::stringstream ss(spec);
  std::string item;
  while (std::getline(ss, item, ';')) {
    if (item.empty()) continue;
    auto eq = item.find('=');
    if (eq == std::string::npos || eq + 2 >= item.size()) continue;
    storage->push_back(item.substr(0, eq));
    const std::string& key = storage->back();
    char ty = item[eq + 1];
    std::string val = item.substr(eq + 3);
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = key.c_str();
    nv.name_size = key.size();
    if (ty == 'i') {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = std::strtoll(val.c_str(), nullptr, 10);
    } else if (ty == 'b') {
      nv.type = PJRT_NamedValue_kBool;
      nv.bool_value = (val == "1" || val == "true");
    } else if (ty == 'f') {
      nv.type = PJRT_NamedValue_kFloat;
      nv.float_value = std::strtof(val.c_str(), nullptr);
    } else {
      storage->push_back(val);
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = storage->back().c_str();
      nv.value_size = storage->back().size();
    }
    out.push_back(nv);
  }
  return out;
}

struct Predictor {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  size_t num_state_args = 0;
  std::vector<PJRT_Buffer*> state_bufs;   // resident params+buffers
  std::vector<HostArray> outputs;         // last run's host results
  std::mutex out_mu;                      // guards `outputs` stores
  size_t num_outputs = 0;

  ~Predictor() {
    if (api) {
      for (auto* b : state_bufs) {
        PJRT_Buffer_Destroy_Args a;
        std::memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        a.buffer = b;
        api->PJRT_Buffer_Destroy(&a);
      }
      if (exec) {
        PJRT_LoadedExecutable_Destroy_Args a;
        std::memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
        a.executable = exec;
        api->PJRT_LoadedExecutable_Destroy(&a);
      }
      if (client) {
        PJRT_Client_Destroy_Args a;
        std::memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
        a.client = client;
        api->PJRT_Client_Destroy(&a);
      }
    }
    // the plugin .so stays loaded for process lifetime (PJRT plugins
    // don't support dlclose-and-reload)
  }

  bool AwaitEvent(PJRT_Event* ev, ErrOut& err) {
    PJRT_Event_Await_Args aa;
    std::memset(&aa, 0, sizeof(aa));
    aa.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    aa.event = ev;
    PJRT_Error* e = api->PJRT_Event_Await(&aa);
    PJRT_Event_Destroy_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    da.event = ev;
    api->PJRT_Event_Destroy(&da);
    if (e) {
      err.set(PjrtErrMessage(api, e));
      return false;
    }
    return true;
  }

  PJRT_Buffer* HostToDevice(const void* data, PJRT_Buffer_Type type,
                            const int64_t* dims, size_t ndim, ErrOut& err) {
    PJRT_Client_BufferFromHostBuffer_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = client;
    a.data = data;
    a.type = type;
    a.dims = dims;
    a.num_dims = ndim;
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
    a.device = device;
    PJRT_Error* e = api->PJRT_Client_BufferFromHostBuffer(&a);
    if (e) {
      err.set(PjrtErrMessage(api, e));
      return nullptr;
    }
    if (a.done_with_host_buffer &&
        !AwaitEvent(a.done_with_host_buffer, err)) {
      return nullptr;
    }
    return a.buffer;
  }

  bool DeviceToHost(PJRT_Buffer* buf, HostArray* out, ErrOut& err) {
    // dims + dtype
    PJRT_Buffer_Dimensions_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    da.buffer = buf;
    RET_IF_ERR(api, api->PJRT_Buffer_Dimensions(&da), err, false);
    out->dims.assign(da.dims, da.dims + da.num_dims);
    PJRT_Buffer_ElementType_Args ta;
    std::memset(&ta, 0, sizeof(ta));
    ta.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    ta.buffer = buf;
    RET_IF_ERR(api, api->PJRT_Buffer_ElementType(&ta), err, false);
    out->dtype_code = PjrtToDtypeCode(ta.type);
    // size query pass (dst == nullptr), then the copy
    PJRT_Buffer_ToHostBuffer_Args ha;
    std::memset(&ha, 0, sizeof(ha));
    ha.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    ha.src = buf;
    RET_IF_ERR(api, api->PJRT_Buffer_ToHostBuffer(&ha), err, false);
    out->data.resize(ha.dst_size);
    ha.dst = out->data.data();
    RET_IF_ERR(api, api->PJRT_Buffer_ToHostBuffer(&ha), err, false);
    if (ha.event && !AwaitEvent(ha.event, err)) return false;
    return true;
  }
};

bool LoadPbin(const std::string& path, std::vector<HostArray>* out,
              ErrOut& err) {
  std::string raw;
  if (!ReadFile(path, &raw)) {
    err.set("cannot read " + path);
    return false;
  }
  const char* p = raw.data();
  const char* end = p + raw.size();
  auto need = [&](size_t n) { return p + n <= end; };
  if (!need(8) || std::memcmp(p, "PTP1", 4) != 0) {
    err.set("bad params.pbin magic");
    return false;
  }
  p += 4;
  uint32_t count;
  std::memcpy(&count, p, 4);
  p += 4;
  for (uint32_t i = 0; i < count; ++i) {
    uint32_t name_len;
    if (!need(4)) return false;
    std::memcpy(&name_len, p, 4);
    p += 4;
    if (!need(name_len)) return false;
    p += name_len;  // names are documentation; binding is positional
    HostArray arr;
    uint32_t ndim;
    if (!need(8)) return false;
    std::memcpy(&arr.dtype_code, p, 4);
    std::memcpy(&ndim, p + 4, 4);
    p += 8;
    arr.dims.resize(ndim);
    if (!need(8 * (ndim + 1))) return false;
    for (uint32_t d = 0; d < ndim; ++d) {
      int64_t v;
      std::memcpy(&v, p, 8);
      arr.dims[d] = v;
      p += 8;
    }
    uint64_t nbytes;
    std::memcpy(&nbytes, p, 8);
    p += 8;
    if (!need(nbytes)) return false;
    arr.data.assign(p, nbytes);
    p += nbytes;
    out->push_back(std::move(arr));
  }
  return true;
}

// One request's host-side results; owned by the caller of ptpred_run2.
struct RunResult {
  std::vector<HostArray> outputs;
};

// Upload inputs, execute, download outputs into `result`. Touches only
// read-only predictor state plus per-call locals — safe to call from
// any number of threads at once.
int RunImpl(Predictor* pred, const void** in_ptrs,
            const uint32_t* in_dtypes, const uint32_t* in_ndims,
            const int64_t* in_dims_flat, int n_inputs,
            std::vector<HostArray>* result, ErrOut& err) {
  const PJRT_Api* api = pred->api;

  auto destroy_buf = [api](PJRT_Buffer* b) {
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = b;
    api->PJRT_Buffer_Destroy(&d);
  };

  std::vector<PJRT_Buffer*> input_bufs;
  size_t dim_ofs = 0;
  for (int i = 0; i < n_inputs; ++i) {
    PJRT_Buffer* b = pred->HostToDevice(
        in_ptrs[i], DtypeCodeToPjrt(in_dtypes[i]), in_dims_flat + dim_ofs,
        in_ndims[i], err);
    if (!b) {  // a failed request must not leak the earlier uploads
      for (auto* ib : input_bufs) destroy_buf(ib);
      return 1;
    }
    dim_ofs += in_ndims[i];
    input_bufs.push_back(b);
  }

  std::vector<PJRT_Buffer*> args(pred->state_bufs);
  args.insert(args.end(), input_bufs.begin(), input_bufs.end());
  PJRT_Buffer* const* arg_list = args.data();

  std::vector<PJRT_Buffer*> outs(pred->num_outputs, nullptr);
  PJRT_Buffer** out_list = outs.data();

  PJRT_ExecuteOptions eo;
  std::memset(&eo, 0, sizeof(eo));
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args ea;
  std::memset(&ea, 0, sizeof(ea));
  ea.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ea.executable = pred->exec;
  ea.options = &eo;
  ea.argument_lists = &arg_list;
  ea.num_devices = 1;
  ea.num_args = args.size();
  ea.output_lists = &out_list;
  ea.execute_device = nullptr;  // single-device: compiled assignment
  PJRT_Error* e = api->PJRT_LoadedExecutable_Execute(&ea);
  for (auto* b : input_bufs) destroy_buf(b);
  if (e) {
    err.set(PjrtErrMessage(api, e));
    return 1;
  }

  result->clear();
  result->resize(pred->num_outputs);
  bool failed = false;
  for (size_t i = 0; i < pred->num_outputs; ++i) {
    // keep destroying the remaining outputs even after a failure —
    // a stream of failing requests must not exhaust device memory
    if (!failed && !pred->DeviceToHost(outs[i], &(*result)[i], err)) {
      failed = true;
    }
    destroy_buf(outs[i]);
  }
  return failed ? 1 : 0;
}

}  // namespace

extern "C" {

void* ptpred_create(const char* plugin_path, const char* options,
                    const char* model_dir, char* errbuf, size_t errlen) {
  ErrOut err{errbuf, errlen};
  auto pred = std::make_unique<Predictor>();

  pred->dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!pred->dl) {
    err.set(std::string("dlopen failed: ") + dlerror());
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api =
      reinterpret_cast<GetApiFn>(dlsym(pred->dl, "GetPjrtApi"));
  if (!get_api) {
    err.set("GetPjrtApi not found in plugin");
    return nullptr;
  }
  pred->api = get_api();

  PJRT_Plugin_Initialize_Args ia;
  std::memset(&ia, 0, sizeof(ia));
  ia.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  RET_IF_ERR(pred->api, pred->api->PJRT_Plugin_Initialize(&ia), err,
             nullptr);

  std::vector<std::string> storage;
  storage.reserve(64);  // stable addresses for NamedValue pointers
  auto nvs = ParseOptions(options ? options : "", &storage);

  PJRT_Client_Create_Args ca;
  std::memset(&ca, 0, sizeof(ca));
  ca.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  ca.create_options = nvs.data();
  ca.num_options = nvs.size();
  RET_IF_ERR(pred->api, pred->api->PJRT_Client_Create(&ca), err, nullptr);
  pred->client = ca.client;

  PJRT_Client_AddressableDevices_Args da;
  std::memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  da.client = pred->client;
  RET_IF_ERR(pred->api, pred->api->PJRT_Client_AddressableDevices(&da),
             err, nullptr);
  if (da.num_addressable_devices == 0) {
    err.set("no addressable devices");
    return nullptr;
  }
  pred->device = da.addressable_devices[0];

  // compile the StableHLO module
  std::string dir(model_dir);
  std::string code, copts;
  if (!ReadFile(dir + "/program.mlir.bc", &code)) {
    err.set("cannot read program.mlir.bc");
    return nullptr;
  }
  ReadFile(dir + "/compile_options.pb", &copts);  // optional
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = code.data();
  prog.code_size = code.size();
  prog.format = "mlir";
  prog.format_size = 4;
  PJRT_Client_Compile_Args cca;
  std::memset(&cca, 0, sizeof(cca));
  cca.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  cca.client = pred->client;
  cca.program = &prog;
  cca.compile_options = copts.data();
  cca.compile_options_size = copts.size();
  RET_IF_ERR(pred->api, pred->api->PJRT_Client_Compile(&cca), err,
             nullptr);
  pred->exec = cca.executable;

  // number of outputs
  PJRT_LoadedExecutable_GetExecutable_Args ga;
  std::memset(&ga, 0, sizeof(ga));
  ga.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ga.loaded_executable = pred->exec;
  RET_IF_ERR(pred->api,
             pred->api->PJRT_LoadedExecutable_GetExecutable(&ga), err,
             nullptr);
  PJRT_Executable_NumOutputs_Args na;
  std::memset(&na, 0, sizeof(na));
  na.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  na.executable = ga.executable;
  RET_IF_ERR(pred->api, pred->api->PJRT_Executable_NumOutputs(&na), err,
             nullptr);
  pred->num_outputs = na.num_outputs;

  // resident state: upload flattened (params, buffers) once
  std::vector<HostArray> state;
  if (!LoadPbin(dir + "/params.pbin", &state, err)) return nullptr;
  pred->num_state_args = state.size();
  for (auto& arr : state) {
    PJRT_Buffer* b = pred->HostToDevice(
        arr.data.data(), DtypeCodeToPjrt(arr.dtype_code),
        arr.dims.data(), arr.dims.size(), err);
    if (!b) return nullptr;
    pred->state_bufs.push_back(b);
  }
  return pred.release();
}

int ptpred_num_outputs(void* h) {
  return static_cast<int>(static_cast<Predictor*>(h)->num_outputs);
}

int ptpred_run(void* h, const void** in_ptrs, const uint32_t* in_dtypes,
               const uint32_t* in_ndims, const int64_t* in_dims_flat,
               int n_inputs, char* errbuf, size_t errlen) {
  ErrOut err{errbuf, errlen};
  auto* pred = static_cast<Predictor*>(h);
  std::vector<HostArray> result;
  int rc = RunImpl(pred, in_ptrs, in_dtypes, in_ndims, in_dims_flat,
                   n_inputs, &result, err);
  if (rc != 0) return rc;
  std::lock_guard<std::mutex> lock(pred->out_mu);
  pred->outputs = std::move(result);
  return 0;
}

void* ptpred_run2(void* h, const void** in_ptrs,
                  const uint32_t* in_dtypes, const uint32_t* in_ndims,
                  const int64_t* in_dims_flat, int n_inputs,
                  char* errbuf, size_t errlen) {
  ErrOut err{errbuf, errlen};
  auto* pred = static_cast<Predictor*>(h);
  auto res = std::make_unique<RunResult>();
  int rc = RunImpl(pred, in_ptrs, in_dtypes, in_ndims, in_dims_flat,
                   n_inputs, &res->outputs, err);
  if (rc != 0) return nullptr;
  return res.release();
}

int ptres_num_outputs(void* r) {
  return static_cast<int>(static_cast<RunResult*>(r)->outputs.size());
}

int ptres_ndim(void* r, int i) {
  auto& o = static_cast<RunResult*>(r)->outputs.at(i);
  return static_cast<int>(o.dims.size());
}

int64_t ptres_dim(void* r, int i, int d) {
  return static_cast<RunResult*>(r)->outputs.at(i).dims.at(d);
}

uint32_t ptres_dtype(void* r, int i) {
  return static_cast<RunResult*>(r)->outputs.at(i).dtype_code;
}

const void* ptres_data(void* r, int i) {
  return static_cast<RunResult*>(r)->outputs.at(i).data.data();
}

int64_t ptres_nbytes(void* r, int i) {
  return static_cast<RunResult*>(r)->outputs.at(i).data.size();
}

void ptres_destroy(void* r) { delete static_cast<RunResult*>(r); }

int ptpred_out_ndim(void* h, int i) {
  auto& o = static_cast<Predictor*>(h)->outputs.at(i);
  return static_cast<int>(o.dims.size());
}

int64_t ptpred_out_dim(void* h, int i, int d) {
  return static_cast<Predictor*>(h)->outputs.at(i).dims.at(d);
}

uint32_t ptpred_out_dtype(void* h, int i) {
  return static_cast<Predictor*>(h)->outputs.at(i).dtype_code;
}

const void* ptpred_out_data(void* h, int i) {
  return static_cast<Predictor*>(h)->outputs.at(i).data.data();
}

int64_t ptpred_out_nbytes(void* h, int i) {
  return static_cast<Predictor*>(h)->outputs.at(i).data.size();
}

void ptpred_destroy(void* h) { delete static_cast<Predictor*>(h); }

}  // extern "C"
