// Shared plumbing for the flat C ABI translation units (predict +
// imperative). Embeds CPython: when the library is loaded from a Python
// process (ctypes) it attaches to the running interpreter; from a plain C
// host it initializes one. C++17 inline variables give every TU the same
// thread-local error slot, so MXGetLastError covers both API surfaces.
#ifndef MXTPU_CAPI_COMMON_H_
#define MXTPU_CAPI_COMMON_H_

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <mutex>
#include <string>
#include <vector>

typedef unsigned int mx_uint;
typedef float mx_float;

namespace mxtpu_capi {

inline thread_local std::string g_last_error;

inline void ensure_python() {
  static std::once_flag once;
  std::call_once(once, []() {
    if (!Py_IsInitialized()) {
      // plain-C host: bring up an interpreter and release the GIL so the
      // per-call PyGILState_Ensure below works from any thread
      Py_InitializeEx(0);
      PyEval_SaveThread();
    }
  });
}

struct GIL {
  PyGILState_STATE st;
  GIL() {
    ensure_python();
    st = PyGILState_Ensure();
  }
  ~GIL() { PyGILState_Release(st); }
};

// capture the pending Python exception into the thread-local error slot
// (reference: c_api_error.cc MXAPISetLastError)
inline void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  g_last_error = "unknown error";
  if (value != nullptr) {
    PyObject *s = PyObject_Str(value);
    if (s != nullptr) {
      const char *msg = PyUnicode_AsUTF8(s);
      if (msg != nullptr) g_last_error = msg;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// NDArrayHandle payload shared by every C-ABI translation unit (handles
// are allocated in one TU and freed in another — a single definition
// here keeps delete size/layout coherent by construction)
struct ND {
  PyObject *obj = nullptr;           // mxnet_tpu.ndarray.NDArray
  std::vector<mx_uint> shape;        // GetShape storage
  std::string bytes;                 // SyncCopyToCPU staging
};

// C string -> Python str via the filesystem default codec
// (surrogateescape round-trips non-UTF-8 bytes — Linux paths and op
// attr values are NOT guaranteed UTF-8; a raw PyUnicode_FromString NULL
// stored into a list crashes the next traversal instead of erroring).
// Appends into `list` at `i`; false with the Python error set on failure.
inline bool set_str_item(PyObject *list, Py_ssize_t i, const char *s) {
  PyObject *u = PyUnicode_DecodeFSDefault(s != nullptr ? s : "");
  if (u == nullptr) return false;
  PyList_SET_ITEM(list, i, u);
  return true;
}

// call <module>.<fn>(*args) -> new ref or nullptr (exception set)
inline PyObject *call_module_fn(const char *module, const char *fn,
                                PyObject *args) {
  PyObject *mod = PyImport_ImportModule(module);
  if (mod == nullptr) return nullptr;
  PyObject *f = PyObject_GetAttrString(mod, fn);
  Py_DECREF(mod);
  if (f == nullptr) return nullptr;
  PyObject *res = PyObject_CallObject(f, args);
  Py_DECREF(f);
  return res;
}

}  // namespace mxtpu_capi

#endif  // MXTPU_CAPI_COMMON_H_
