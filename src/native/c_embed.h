// Shared CPython-embedding glue for the mxnet_tpu C ABI libraries.
//
// The TPU-native runtime that can execute the framework's artifacts is
// jax/XLA, so the C ABI embeds the CPython interpreter and drives the
// Python package through the C API; host processes see only flat C
// functions and opaque handles (the reference's handle-based C ABI shape,
// include/mxnet/c_api.h).  Each entry point takes the GIL, so handles may
// be used from any host thread.
#ifndef MXTPU_C_EMBED_H_
#define MXTPU_C_EMBED_H_

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdlib>
#include <mutex>
#include <string>

namespace mxtpu {

inline std::string &last_error() {
  static std::string err;
  return err;
}

inline std::mutex &err_mutex() {
  static std::mutex m;
  return m;
}

inline void set_error(const std::string &msg) {
  std::lock_guard<std::mutex> lock(err_mutex());
  last_error() = msg;
}

// Capture the current Python exception into the error string.
inline void set_error_from_python() {
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

inline void ensure_interpreter() {
  static std::once_flag once;
  std::call_once(once, []() {
    if (!Py_IsInitialized()) {
      Py_InitializeEx(0);  // no signal handlers: we are a guest runtime
      PyEval_SaveThread();  // release the init-held GIL for host threads
    }
  });
}

class Gil {
 public:
  Gil() { state_ = PyGILState_Ensure(); }
  ~Gil() { PyGILState_Release(state_); }
  Gil(const Gil &) = delete;
  Gil &operator=(const Gil &) = delete;

 private:
  PyGILState_STATE state_;
};

// Pin the jax platform from MXTPU_C_PLATFORM before the first backend
// touch — for a host process that must leave the chip to another process
// (a chip belongs to one process at a time).
inline bool pin_platform() {
  const char *platform = std::getenv("MXTPU_C_PLATFORM");
  if (platform == nullptr || platform[0] == '\0') return true;
  std::string code = "import jax\njax.config.update('jax_platforms', '";
  code += platform;
  code += "')\n";
  if (PyRun_SimpleString(code.c_str()) != 0) {
    set_error("failed to pin jax platform");
    return false;
  }
  return true;
}

}  // namespace mxtpu

#endif  // MXTPU_C_EMBED_H_
