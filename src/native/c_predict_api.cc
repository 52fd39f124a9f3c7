// C predict ABI over the StableHLO deployment artifact.
//
// Reference: include/mxnet/c_predict_api.h (MXPredCreate / MXPredForward /
// MXPredGetOutput ...) — the C surface embedded apps link against.
//
// TPU-native re-design: the deployable artifact is a serialized StableHLO
// program + params (mxnet_tpu/deploy.py), and the portable runtime that can
// execute it is jax/XLA — so this library embeds the CPython interpreter
// and drives mxnet_tpu.deploy.load_model through the Python C API.  The
// exported symbols form a stable C ABI: a C/C++/Rust/Go host process needs
// only this header-free surface (dlopen + dlsym works too) and never sees
// Python types.
//
// Thread-safety: every entry point takes the GIL via PyGILState_Ensure, so
// handles may be used from any host thread (calls serialize on the GIL,
// like the reference's per-predictor lock, c_predict_api.cc).
//
// Build: make -C src/native c_api   (links against libpython3).

#include <Python.h>

#include <cstring>
#include <mutex>
#include <string>
#include <vector>

namespace {

std::string g_last_error;
std::mutex g_err_mutex;

void set_error(const std::string &msg) {
  std::lock_guard<std::mutex> lock(g_err_mutex);
  g_last_error = msg;
}

// Capture the current Python exception into the error string.
void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  std::string msg = "python error";
  if (value != nullptr) {
    PyObject *s = PyObject_Str(value);
    if (s != nullptr) {
      const char *c = PyUnicode_AsUTF8(s);
      if (c != nullptr) msg = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  set_error(msg);
}

struct Predictor {
  PyObject *predictor = nullptr;  // mxnet_tpu.deploy.StableHLOPredictor
  PyObject *input = nullptr;      // staged numpy input
  PyObject *output = nullptr;     // contiguous float32 numpy output
};

std::once_flag g_init_once;

void ensure_interpreter() {
  std::call_once(g_init_once, []() {
    if (!Py_IsInitialized()) {
      Py_InitializeEx(0);  // no signal handlers: we are a guest runtime
      // release the GIL acquired by initialization so host threads can
      // enter through PyGILState_Ensure
      PyEval_SaveThread();
    }
  });
}

class Gil {
 public:
  Gil() { state_ = PyGILState_Ensure(); }
  ~Gil() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

}  // namespace

extern "C" {

const char *MXTpuGetLastError() {
  std::lock_guard<std::mutex> lock(g_err_mutex);
  return g_last_error.c_str();
}

// Create a predictor from a deploy.export_model prefix
// (<prefix>-model.stablehlo / -meta.json / -params.npz).
int MXTpuPredCreate(const char *prefix, void **out_handle) {
  ensure_interpreter();
  Gil gil;
  // MXTPU_C_PLATFORM pins the jax backend (e.g. "cpu") BEFORE the first
  // backend touch — for a host process that must leave the chip to
  // another process (a chip belongs to one process at a time).
  const char *platform = std::getenv("MXTPU_C_PLATFORM");
  if (platform != nullptr && platform[0] != '\0') {
    std::string code = "import jax\njax.config.update('jax_platforms', '";
    code += platform;
    code += "')\n";
    if (PyRun_SimpleString(code.c_str()) != 0) {
      set_error("failed to pin jax platform");
      return -1;
    }
  }
  PyObject *mod = PyImport_ImportModule("mxnet_tpu.deploy");
  if (mod == nullptr) {
    set_error_from_python();
    return -1;
  }
  PyObject *pred =
      PyObject_CallMethod(mod, "load_model", "s", prefix);
  Py_DECREF(mod);
  if (pred == nullptr) {
    set_error_from_python();
    return -1;
  }
  auto *p = new Predictor();
  p->predictor = pred;
  *out_handle = p;
  return 0;
}

// Stage a float32 input of `size` elements with the given shape.
int MXTpuPredSetInput(void *handle, const float *data, const long *shape,
                      int ndim) {
  auto *p = static_cast<Predictor *>(handle);
  Gil gil;
  PyObject *np = PyImport_ImportModule("numpy");
  if (np == nullptr) {
    set_error_from_python();
    return -1;
  }
  long total = 1;
  PyObject *shp = PyTuple_New(ndim);
  for (int i = 0; i < ndim; ++i) {
    total *= shape[i];
    PyTuple_SET_ITEM(shp, i, PyLong_FromLong(shape[i]));
  }
  PyObject *bytes = PyBytes_FromStringAndSize(
      reinterpret_cast<const char *>(data),
      static_cast<Py_ssize_t>(total * sizeof(float)));
  PyObject *flat = PyObject_CallMethod(np, "frombuffer", "Os", bytes,
                                       "float32");
  PyObject *arr =
      flat ? PyObject_CallMethod(flat, "reshape", "O", shp) : nullptr;
  Py_XDECREF(flat);
  Py_DECREF(bytes);
  Py_DECREF(shp);
  Py_DECREF(np);
  if (arr == nullptr) {
    set_error_from_python();
    return -1;
  }
  Py_XDECREF(p->input);
  p->input = arr;
  return 0;
}

int MXTpuPredForward(void *handle) {
  auto *p = static_cast<Predictor *>(handle);
  Gil gil;
  if (p->input == nullptr) {
    set_error("MXTpuPredForward: no input staged");
    return -1;
  }
  PyObject *out =
      PyObject_CallMethod(p->predictor, "predict", "O", p->input);
  if (out == nullptr) {
    set_error_from_python();
    return -1;
  }
  // force float32 C-contiguous so GetOutput is one memcpy
  PyObject *np = PyImport_ImportModule("numpy");
  PyObject *contig =
      np ? PyObject_CallMethod(np, "ascontiguousarray", "Os", out,
                               "float32")
         : nullptr;
  Py_XDECREF(np);
  Py_DECREF(out);
  if (contig == nullptr) {
    set_error_from_python();
    return -1;
  }
  Py_XDECREF(p->output);
  p->output = contig;
  return 0;
}

int MXTpuPredGetOutputShape(void *handle, long *dims, int max_ndim,
                            int *out_ndim) {
  auto *p = static_cast<Predictor *>(handle);
  Gil gil;
  if (p->output == nullptr) {
    set_error("MXTpuPredGetOutputShape: forward not run");
    return -1;
  }
  PyObject *shape = PyObject_GetAttrString(p->output, "shape");
  if (shape == nullptr) {
    set_error_from_python();
    return -1;
  }
  Py_ssize_t n = PyTuple_Size(shape);
  *out_ndim = static_cast<int>(n);
  if (n > max_ndim) {
    Py_DECREF(shape);
    set_error("MXTpuPredGetOutputShape: dims buffer too small");
    return -1;  // caller sees the required ndim in *out_ndim
  }
  for (Py_ssize_t i = 0; i < n; ++i) {
    dims[i] = PyLong_AsLong(PyTuple_GetItem(shape, i));
  }
  Py_DECREF(shape);
  return 0;
}

int MXTpuPredGetOutput(void *handle, float *buf, long size) {
  auto *p = static_cast<Predictor *>(handle);
  Gil gil;
  if (p->output == nullptr) {
    set_error("MXTpuPredGetOutput: forward not run");
    return -1;
  }
  PyObject *bytes = PyObject_CallMethod(p->output, "tobytes", nullptr);
  if (bytes == nullptr) {
    set_error_from_python();
    return -1;
  }
  char *src = nullptr;
  Py_ssize_t nbytes = 0;
  if (PyBytes_AsStringAndSize(bytes, &src, &nbytes) != 0) {
    Py_DECREF(bytes);
    set_error_from_python();
    return -1;
  }
  if (nbytes > size * static_cast<long>(sizeof(float))) {
    Py_DECREF(bytes);
    set_error("MXTpuPredGetOutput: buffer too small");
    return -1;
  }
  std::memcpy(buf, src, static_cast<size_t>(nbytes));
  Py_DECREF(bytes);
  return 0;
}

int MXTpuPredFree(void *handle) {
  auto *p = static_cast<Predictor *>(handle);
  {
    Gil gil;
    Py_XDECREF(p->predictor);
    Py_XDECREF(p->input);
    Py_XDECREF(p->output);
  }
  delete p;
  return 0;
}

}  // extern "C"
