// Python-free native predictor over the PJRT C API.
//
// Capability parity with the reference's C++ inference entry
// (inference/io.h:35 LoadInferenceModel; api_impl.cc:64
// NativePaddlePredictor::Init — load a saved model + params and run it
// from C++ with no Python in the process). Our export artifact
// (io.py save_inference_model) is:
//   model.mlir   — raw StableHLO bytecode of the inference function
//   params.npz / state.npz — weights (uncompressed zip of .npy members)
//   meta.json    — ordered flat input signature: which npz member (or
//                  runtime feed) supplies each executable argument
// This binary dlopens a PJRT plugin (libtpu.so on TPU hosts; any
// GetPjrtApi-exporting .so), compiles the StableHLO, stages weights and
// feeds as device buffers, executes, and prints per-output checksums.
//
//   predictor <artifact_dir> <plugin.so> [--probe]
//
// --probe stops after the Python-free half that needs no accelerator:
// plugin dlopen + PJRT version handshake + full artifact load/validation
// (meta.json vs npz shapes/dtypes/sizes). The full run requires a local
// device for the plugin (see DESIGN.md "native predictor").
//
// Build (test_native_predictor.py does this):
//   g++ -O2 -std=c++17 -I$TF_INCLUDE predictor.cc -o predictor -ldl

#include "pjrt_common.h"

int main(int argc, char** argv) {
  g_tool = "predictor";
  if (argc < 3) {
    fprintf(stderr,
            "usage: predictor <artifact_dir> <pjrt_plugin.so> [--probe]\n");
    return 2;
  }
  std::string dir = argv[1], plugin = argv[2];
  bool probe = argc > 3 && std::string(argv[3]) == "--probe";

  // ---- artifact load + validation (no accelerator needed) ---------------
  std::string mlir = ReadFileOrDie(dir + "/model.mlir");
  std::string meta = ReadFileOrDie(dir + "/meta.json");
  std::string params_blob = ReadFileOrDie(dir + "/params.npz");
  std::string state_blob = ReadFileOrDie(dir + "/state.npz");
  auto params = ParseNpz(params_blob, "params.npz");
  std::map<std::string, Array> state;
  if (state_blob.size() > 4 && rd32(state_blob.data()) == 0x04034b50)
    state = ParseNpz(state_blob, "state.npz");
  auto inputs = ParseMetaInputs(meta);

  size_t feed_args = 0, weight_bytes = 0;
  for (const auto& sp : inputs) {
    DType dt = DtypeOrDie(sp.dtype);
    size_t want = dt.size;
    for (int64_t d : sp.shape) want *= size_t(d);
    if (sp.source == "feed") { ++feed_args; continue; }
    auto& table = sp.source == "params.npz" ? params : state;
    auto it = table.find(sp.name);
    if (it == table.end()) Die("meta input " + sp.name + " missing from " +
                               sp.source);
    const Array& got = it->second;
    if (got.nbytes != want)
      Die("weight " + sp.name + " is " + std::to_string(got.nbytes) +
          " bytes, signature expects " + std::to_string(want));
    if (got.dtype != dt.npy)
      Die("weight " + sp.name + " stored as npy '" + got.dtype +
          "', signature expects '" + dt.npy + "' (" + sp.dtype + ")");
    if (got.shape != sp.shape) {
      std::string g, w;
      for (int64_t v : got.shape) g += std::to_string(v) + ",";
      for (int64_t v : sp.shape) w += std::to_string(v) + ",";
      Die("weight " + sp.name + " has shape [" + g +
          "], signature expects [" + w + "]");
    }
    weight_bytes += want;
  }
  fprintf(stderr,
          "predictor: artifact ok — %zu args (%zu weights %.1f MB, %zu feeds), "
          "stablehlo %zu bytes\n",
          inputs.size(), inputs.size() - feed_args,
          weight_bytes / 1048576.0, feed_args, mlir.size());

  // ---- plugin handshake -------------------------------------------------
  void* lib = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!lib) Die(std::string("dlopen failed: ") + dlerror());
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(lib, "GetPjrtApi"));
  if (!get_api) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();
  if (!g_api) Die("GetPjrtApi returned null");
  fprintf(stderr, "predictor: plugin PJRT API v%d.%d (header v%d.%d)\n",
          g_api->pjrt_api_version.major_version,
          g_api->pjrt_api_version.minor_version, PJRT_API_MAJOR,
          PJRT_API_MINOR);
  if (g_api->pjrt_api_version.major_version != PJRT_API_MAJOR)
    Die("PJRT major version mismatch");

  if (probe) {
    printf("PROBE OK\n");
    return 0;
  }

  PJRT_Plugin_Initialize_Args pi;
  memset(&pi, 0, sizeof pi);
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  Check(g_api->PJRT_Plugin_Initialize(&pi), "plugin init");

  PJRT_Client_Create_Args cc;
  memset(&cc, 0, sizeof cc);
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  Check(g_api->PJRT_Client_Create(&cc), "client create");
  PJRT_Client* client = cc.client;

  PJRT_Client_AddressableDevices_Args ad;
  memset(&ad, 0, sizeof ad);
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = client;
  Check(g_api->PJRT_Client_AddressableDevices(&ad), "devices");
  if (ad.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* dev = ad.addressable_devices[0];
  fprintf(stderr, "predictor: %zu addressable device(s)\n",
          ad.num_addressable_devices);

  // ---- compile ----------------------------------------------------------
  PJRT_Program prog;
  memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = mlir.data();
  prog.code_size = mlir.size();
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = 4;
  std::string copts = MinimalCompileOptions();
  PJRT_Client_Compile_Args comp;
  memset(&comp, 0, sizeof comp);
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = client;
  comp.program = &prog;
  comp.compile_options = copts.data();
  comp.compile_options_size = copts.size();
  Check(g_api->PJRT_Client_Compile(&comp), "compile");
  fprintf(stderr, "predictor: stablehlo compiled\n");

  // ---- stage inputs (weights from npz; feeds zero-filled or from
  //      <dir>/feed_<name>.npy if present) --------------------------------
  std::vector<PJRT_Buffer*> arg_bufs;
  std::vector<std::string> feed_storage;
  for (const auto& sp : inputs) {
    DType dt = DtypeOrDie(sp.dtype);
    size_t nbytes = dt.size;
    for (int64_t d : sp.shape) nbytes *= size_t(d);
    const char* data;
    if (sp.source == "feed") {
      std::string path = dir + "/feed_" + sp.name + ".npy";
      FILE* f = fopen(path.c_str(), "rb");
      if (f) {
        fclose(f);
        std::string blob = ReadFileOrDie(path);
        feed_storage.push_back(std::move(blob));
        Array a = ParseNpy(feed_storage.back().data(),
                           feed_storage.back().size(), path);
        if (a.nbytes != nbytes) Die("feed " + sp.name + " wrong size");
        if (a.dtype != dt.npy)
          Die("feed " + sp.name + " is npy '" + a.dtype + "', signature "
              "expects '" + dt.npy + "' (" + sp.dtype + ")");
        if (a.shape != sp.shape) Die("feed " + sp.name + " wrong shape");
        data = a.data;
      } else {
        feed_storage.emplace_back(nbytes, '\0');
        data = feed_storage.back().data();
      }
    } else {
      auto& table = sp.source == "params.npz" ? params : state;
      data = table.at(sp.name).data;
    }
    PJRT_Client_BufferFromHostBuffer_Args hb;
    memset(&hb, 0, sizeof hb);
    hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    hb.client = client;
    hb.data = data;
    hb.type = dt.pjrt;
    hb.dims = sp.shape.data();
    hb.num_dims = sp.shape.size();
    hb.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    hb.device = dev;
    Check(g_api->PJRT_Client_BufferFromHostBuffer(&hb),
          ("h2d " + sp.name).c_str());
    AwaitAndDestroy(hb.done_with_host_buffer, "h2d done");
    arg_bufs.push_back(hb.buffer);
  }

  // ---- execute ----------------------------------------------------------
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  memset(&ge, 0, sizeof ge);
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = comp.executable;
  Check(g_api->PJRT_LoadedExecutable_GetExecutable(&ge), "get executable");
  PJRT_Executable_NumOutputs_Args no;
  memset(&no, 0, sizeof no);
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  Check(g_api->PJRT_Executable_NumOutputs(&no), "num outputs");

  std::vector<PJRT_Buffer*> outs(no.num_outputs, nullptr);
  PJRT_Buffer** out_list = outs.data();
  PJRT_Buffer* const* arg_list = arg_bufs.data();
  PJRT_ExecuteOptions eo;
  memset(&eo, 0, sizeof eo);
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  PJRT_Event* done = nullptr;
  PJRT_LoadedExecutable_Execute_Args ex;
  memset(&ex, 0, sizeof ex);
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = comp.executable;
  ex.options = &eo;
  ex.argument_lists = &arg_list;
  ex.num_devices = 1;
  ex.num_args = arg_bufs.size();
  ex.output_lists = &out_list;
  ex.device_complete_events = &done;
  ex.execute_device = dev;
  Check(g_api->PJRT_LoadedExecutable_Execute(&ex), "execute");
  AwaitAndDestroy(done, "execute done");

  // ---- fetch outputs, print checksums ------------------------------------
  for (size_t i = 0; i < outs.size(); ++i) {
    PJRT_Buffer_ToHostBuffer_Args th;
    memset(&th, 0, sizeof th);
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = outs[i];
    Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "d2h size query");
    std::vector<char> host(th.dst_size);
    th.dst = host.data();
    Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "d2h");
    AwaitAndDestroy(th.event, "d2h done");
    PJRT_Buffer_ElementType_Args et;
    memset(&et, 0, sizeof et);
    et.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    et.buffer = outs[i];
    Check(g_api->PJRT_Buffer_ElementType(&et), "element type");
    double sum = 0;
    if (et.type == PJRT_Buffer_Type_F32) {
      const float* v = reinterpret_cast<const float*>(host.data());
      for (size_t k = 0; k < host.size() / 4; ++k) sum += v[k];
    }
    printf("OUTPUT %zu bytes=%zu f32sum=%.6f\n", i, host.size(), sum);
  }
  printf("RUN OK\n");
  return 0;
}
