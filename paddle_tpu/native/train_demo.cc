// C++ training demo — the reference's python-free trainer entry
// (train/demo/demo_trainer.cc + train/test_train_recognize_digits.cc).
//
// The reference's demo loads a ProgramDesc and drives its C++ Executor.
// Our runtime is XLA/PJRT, whose only in-image entry point is the Python
// binding (no standalone PJRT C library ships here), so this binary
// embeds libpython *solely as the PJRT loader*: every piece of driver
// logic — synthetic data generation, RecordIO writing/scanning
// (native/recordio.cc, the same C API the ctypes binding uses),
// batching, the epoch loop, loss tracking, convergence check — is C++.
// The embedded interpreter is handed one fixed train-step callable and
// receives raw batch bytes.
//
// Build & run (see tests/test_train_demo.py):
//   g++ -O3 -std=c++17 train_demo.cc recordio.cc \
//       $(python3-config --includes) $(python3-config --embed --ldflags) \
//       -lz -o train_demo
//   JAX_PLATFORMS=cpu ./train_demo

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <unistd.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

// recordio C API (native/recordio.cc)
extern "C" {
void* rio_writer_open(const char* path, int compress, int chunk_bytes);
int rio_writer_write(void* handle, const uint8_t* data, uint32_t len);
int rio_writer_close(void* handle);
void* rio_scanner_open(const char* path);
int64_t rio_scanner_next(void* handle, const uint8_t** out);
void rio_scanner_close(void* handle);
}

namespace {

constexpr int kFeature = 64;   // compact mnist-like task: fast CPU jit
constexpr int kClasses = 10;
constexpr int kSamples = 1024;
constexpr int kBatch = 64;
constexpr int kEpochs = 4;

// deterministic LCG so the demo is reproducible without <random> seeding
// differences across libstdc++ versions
struct Lcg {
  uint64_t s;
  explicit Lcg(uint64_t seed) : s(seed) {}
  uint64_t next() { return s = s * 6364136223846793005ull + 1442695040888963407ull; }
  float unit() { return (next() >> 40) / float(1 << 24); }        // [0,1)
  float gauss() {  // sum of uniforms: cheap, good enough for a demo
    float a = 0;
    for (int i = 0; i < 4; ++i) a += unit();
    return (a - 2.0f) * 1.73f;
  }
};

struct Record {       // one sample: features then label
  float x[kFeature];
  int64_t y;
};

std::string WriteDataset(const char* path) {
  // class-dependent means -> linearly separable, so SGD provably learns
  Lcg centers_rng(7);
  std::vector<float> centers(kClasses * kFeature);
  for (auto& c : centers) c = centers_rng.gauss();

  void* w = rio_writer_open(path, /*compress=*/1, /*chunk_bytes=*/1 << 16);
  if (!w) return "rio_writer_open failed";
  Lcg noise(13);
  Record r;
  for (int i = 0; i < kSamples; ++i) {
    r.y = i % kClasses;
    for (int j = 0; j < kFeature; ++j)
      r.x[j] = centers[r.y * kFeature + j] + 0.5f * noise.gauss();
    if (rio_writer_write(w, reinterpret_cast<const uint8_t*>(&r), sizeof(r)) != 0)
      return "rio_writer_write failed";
  }
  if (rio_writer_close(w) != 0) return "rio_writer_close failed";
  return "";
}

// the only python the demo runs: build the model once, expose _step()
const char* kBootstrap = R"PY(
import jax
import numpy as np
import paddle_tpu as pt
from paddle_tpu import layers, optimizer as opt

_FEATURE, _CLASSES = 64, 10

def _net(image, label):
    h = layers.fc(image, 128, act="relu", name="fc1")
    logits = layers.fc(h, _CLASSES, name="fc2")
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    return {"loss": loss}

_prog = pt.build(_net)
_trainer = pt.Trainer(_prog, opt.SGD(0.1), loss_name="loss")
_started = False

def _step(batch_bytes, batch_size):
    global _started
    rec = np.frombuffer(batch_bytes, dtype=np.uint8).reshape(batch_size, -1)
    img = rec[:, :_FEATURE * 4].copy().view(np.float32)
    lab = rec[:, _FEATURE * 4:].copy().view(np.int64)
    feed = {"image": img, "label": lab}
    if not _started:
        _trainer.startup(sample_feed=feed)
        _started = True
    return float(_trainer.step(feed)["loss"])
)PY";

}  // namespace

struct FileGuard {  // remove the temp dataset on every exit path
  const char* path;
  ~FileGuard() { std::remove(path); }
};

int main() {
  // pid-tagged path so concurrent runs don't rewrite each other's data
  char data_path[128];
  std::snprintf(data_path, sizeof(data_path),
                "/tmp/paddle_tpu_train_demo.%d.recordio", (int)getpid());
  FileGuard guard{data_path};
  std::string err = WriteDataset(data_path);
  if (!err.empty()) {
    std::fprintf(stderr, "dataset: %s\n", err.c_str());
    return 1;
  }

  Py_Initialize();
  if (PyRun_SimpleString(kBootstrap) != 0) {
    std::fprintf(stderr, "bootstrap failed\n");
    return 1;
  }
  PyObject* main_mod = PyImport_AddModule("__main__");
  PyObject* step_fn = PyObject_GetAttrString(main_mod, "_step");
  if (!step_fn) {
    std::fprintf(stderr, "_step not found\n");
    return 1;
  }

  double first_epoch_loss = -1, last_epoch_loss = -1;
  std::vector<uint8_t> batch;
  for (int epoch = 0; epoch < kEpochs; ++epoch) {
    void* s = rio_scanner_open(data_path);
    if (!s) {
      std::fprintf(stderr, "rio_scanner_open failed\n");
      return 1;
    }
    double total = 0;
    int batches = 0, in_batch = 0;
    const uint8_t* rec = nullptr;
    int64_t n;
    batch.clear();
    while ((n = rio_scanner_next(s, &rec)) > 0) {
      if (n != sizeof(Record)) {
        std::fprintf(stderr, "bad record size %lld\n", (long long)n);
        return 1;
      }
      batch.insert(batch.end(), rec, rec + n);
      if (++in_batch == kBatch) {
        PyObject* res = PyObject_CallFunction(
            step_fn, "y#i", reinterpret_cast<const char*>(batch.data()),
            (Py_ssize_t)batch.size(), kBatch);
        if (!res) {
          PyErr_Print();
          return 1;
        }
        total += PyFloat_AsDouble(res);
        Py_DECREF(res);
        ++batches;
        in_batch = 0;
        batch.clear();
      }
    }
    rio_scanner_close(s);
    if (n == -2) {                 // recordio.cc: -1 = EOF, -2 = corruption
      std::fprintf(stderr, "recordio corruption in %s\n", data_path);
      return 1;
    }
    if (batches == 0) {
      std::fprintf(stderr, "no complete batches read\n");
      return 1;
    }
    double avg = total / batches;
    std::printf("epoch %d: avg_loss=%.4f (%d batches)\n", epoch, avg, batches);
    if (epoch == 0) first_epoch_loss = avg;
    last_epoch_loss = avg;
  }

  Py_DECREF(step_fn);
  Py_Finalize();

  if (last_epoch_loss < first_epoch_loss * 0.5) {
    std::printf("PASS: loss %.4f -> %.4f\n", first_epoch_loss, last_epoch_loss);
    return 0;
  }
  std::printf("FAIL: loss %.4f -> %.4f\n", first_epoch_loss, last_epoch_loss);
  return 2;
}
