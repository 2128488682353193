// Drives the JNI gateway shims (jni/blaze_jni.cc) end to end WITHOUT
// a JVM (round-4 verdict item #6: the shims were gated on a JDK the
// image lacks and had never compiled or run).
//
// A fake JNINativeInterface_ function table stands in for the JVM:
// GetMethodID resolves the three wrapper methods by name,
// CallObjectMethodV serves the TaskDefinition bytes,
// CallVoidMethodV(importBatch) imports the Arrow C-FFI batch the
// gateway exports — i.e. the exact call sequence
// BlazeCallNativeWrapper drives through JniBridge
// (JniBridge.java:32-36 in the reference):
//
//   callNative(budget, wrapper) -> nextBatch(ptr)* -> finalizeNative
//
// Because the table layout follows the public JNI spec (see
// jni_stub/jni.h), the same shim binary is what a real JVM would call.

// asserts ARE the test's checks — keep them in every build config
#undef NDEBUG

#include <jni.h>
#include <Python.h>

#include <cassert>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "blaze_native.h"

// exported by libblaze_jni
extern "C" {
jint JNI_OnLoad(JavaVM* vm, void*);
jlong Java_org_blaze_1tpu_JniBridge_callNative(JNIEnv*, jclass, jlong,
                                               jobject);
jboolean Java_org_blaze_1tpu_JniBridge_nextBatch(JNIEnv*, jclass, jlong);
void Java_org_blaze_1tpu_JniBridge_finalizeNative(JNIEnv*, jclass, jlong);
}

// ---- the "JVM": one wrapper object + method handles ----------------------

struct FakeWrapper {
  std::string td;                      // getRawTaskDefinition()
  std::vector<int64_t> y;              // importBatch captures
  std::vector<uint8_t> y_valid;
  std::vector<std::string> u;
  std::vector<uint8_t> u_valid;
  std::string error;                   // setError / ThrowNew
  int global_refs = 0;
};

static _jmethodID* const MID_GET_TD = (_jmethodID*)0x101;
static _jmethodID* const MID_IMPORT = (_jmethodID*)0x102;
static _jmethodID* const MID_SET_ERROR = (_jmethodID*)0x103;
static _jobject* const FAKE_CLASS = (_jobject*)0x201;
static _jobject* const FAKE_BYTES = (_jobject*)0x202;

static FakeWrapper* unwrap(jobject o) { return (FakeWrapper*)o; }

static jclass fake_FindClass(JNIEnv*, const char*) { return FAKE_CLASS; }

static jint fake_ThrowNew(JNIEnv*, jclass, const char* msg) {
  std::fprintf(stderr, "thrown: %s\n", msg ? msg : "?");
  return 0;
}

static jobject fake_NewGlobalRef(JNIEnv*, jobject o) {
  if (o != FAKE_CLASS) unwrap(o)->global_refs++;
  return o;
}

static void fake_DeleteGlobalRef(JNIEnv*, jobject o) {
  if (o != FAKE_CLASS) unwrap(o)->global_refs--;
}

static jclass fake_GetObjectClass(JNIEnv*, jobject) { return FAKE_CLASS; }

static jmethodID fake_GetMethodID(JNIEnv*, jclass, const char* name,
                                  const char* sig) {
  if (!std::strcmp(name, "getRawTaskDefinition")) {
    assert(!std::strcmp(sig, "()[B"));
    return MID_GET_TD;
  }
  if (!std::strcmp(name, "importBatch")) {
    assert(!std::strcmp(sig, "(J)V"));
    return MID_IMPORT;
  }
  if (!std::strcmp(name, "setError")) return MID_SET_ERROR;
  return nullptr;
}

static jobject fake_CallObjectMethodV(JNIEnv*, jobject, jmethodID m,
                                      va_list) {
  assert(m == MID_GET_TD);
  return FAKE_BYTES;
}

static void import_batch(FakeWrapper* w, uintptr_t addr) {
  auto* fb = (bt_ffi_batch*)addr;
  assert(fb->n_cols == 2);
  int64_t n = fb->arrays[0].length;

  std::vector<int64_t> data((size_t)n);
  std::vector<uint8_t> valid((size_t)n);
  int rc = bt_arrow_import_primitive(&fb->schemas[0], &fb->arrays[0],
                                     data.data(), valid.data(), n);
  assert(rc == 0);
  for (int64_t i = 0; i < n; i++) {
    w->y.push_back(data[(size_t)i]);
    w->y_valid.push_back(valid[(size_t)i]);
  }
  const int32_t W = 8;
  std::vector<uint8_t> sdata((size_t)(n * W));
  std::vector<int32_t> slens((size_t)n);
  std::vector<uint8_t> svalid((size_t)n);
  rc = bt_arrow_import_string(&fb->schemas[1], &fb->arrays[1], sdata.data(),
                              slens.data(), svalid.data(), n, W);
  assert(rc == 0);
  for (int64_t i = 0; i < n; i++) {
    w->u.emplace_back((const char*)&sdata[(size_t)(i * W)],
                      (size_t)slens[(size_t)i]);
    w->u_valid.push_back(svalid[(size_t)i]);
  }
  for (int64_t c = 0; c < fb->n_cols; c++) {
    if (fb->arrays[c].release) fb->arrays[c].release(&fb->arrays[c]);
    if (fb->schemas[c].release) fb->schemas[c].release(&fb->schemas[c]);
  }
}

static void fake_CallVoidMethodV(JNIEnv*, jobject obj, jmethodID m,
                                 va_list args) {
  FakeWrapper* w = unwrap(obj);
  if (m == MID_IMPORT) {
    import_batch(w, (uintptr_t)va_arg(args, jlong));
  } else if (m == MID_SET_ERROR) {
    jstring s = va_arg(args, jstring);
    w->error = s ? (const char*)s : "";
  }
}

static jstring fake_NewStringUTF(JNIEnv*, const char* s) {
  // handle IS the (interned) chars: CallVoidMethodV reads them back
  static std::vector<std::string> pool;
  pool.emplace_back(s ? s : "");
  return (jstring)pool.back().c_str();
}

static FakeWrapper* g_active = nullptr;

static jsize fake_GetArrayLength(JNIEnv*, jarray a) {
  assert(a == FAKE_BYTES);
  return (jsize)g_active->td.size();
}

static jbyte* fake_GetByteArrayElements(JNIEnv*, jbyteArray a, jboolean* c) {
  assert(a == FAKE_BYTES);
  if (c) *c = JNI_FALSE;
  return (jbyte*)g_active->td.data();
}

static void fake_ReleaseByteArrayElements(JNIEnv*, jbyteArray, jbyte*, jint) {}

static jboolean fake_ExceptionCheck(JNIEnv*) { return JNI_FALSE; }

static PyObject* run_py(const char* code, const char* result_name) {
  PyObject* main_mod = PyImport_AddModule("__main__");
  PyObject* globals = PyModule_GetDict(main_mod);
  PyObject* r = PyRun_String(code, Py_file_input, globals, globals);
  if (!r) {
    PyErr_Print();
    return nullptr;
  }
  Py_DECREF(r);
  return result_name ? PyDict_GetItemString(globals, result_name) : Py_None;
}

int main(int argc, char** argv) {
  const char* repo = argc > 1 ? argv[1] : REPO_ROOT;
  setenv("JAX_PLATFORMS", "cpu", 1);

  Py_InitializeEx(0);
  {
    std::string boot = std::string("import sys; sys.path.insert(0, '") + repo +
                       "')\n"
                       "import jax\n"
                       "jax.config.update('jax_platforms', 'cpu')\n"
                       "jax.config.update('jax_enable_x64', True)\n";
    if (!run_py(boot.c_str(), nullptr)) return 1;
  }
  const char* build_task =
      "from blaze_tpu.batch import batch_from_pydict\n"
      "from blaze_tpu.schema import DataType, Field, Schema\n"
      "from blaze_tpu.ops import MemoryScanExec, ProjectExec\n"
      "from blaze_tpu.exprs import col, lit\n"
      "from blaze_tpu.exprs.ir import ScalarFunc\n"
      "from blaze_tpu.serde.to_proto import task_definition\n"
      "schema = Schema([Field('x', DataType.int64()), Field('s', DataType.string(8))])\n"
      "b = batch_from_pydict({'x': [1, 2, None, 4], 's': ['ab', 'cd', None, 'ef']}, schema)\n"
      "plan = ProjectExec(MemoryScanExec([[b]], schema), [\n"
      "    (col('x') + lit(10)).alias('y'),\n"
      "    ScalarFunc('upper', [col('s')]).alias('u'),\n"
      "])\n"
      "td = task_definition(plan, 'jni-ctest', 0, 0)\n";
  PyObject* td = run_py(build_task, "td");
  if (!td || !PyBytes_Check(td)) {
    std::fprintf(stderr, "FAIL: task definition build\n");
    return 1;
  }

  FakeWrapper wrapper;
  wrapper.td.assign(PyBytes_AsString(td), (size_t)PyBytes_Size(td));
  g_active = &wrapper;

  // hand the GIL to the gateway producer thread (blaze_jni's call_once
  // sees the interpreter already initialized and skips its own init)
  PyEval_SaveThread();

  JNINativeInterface_ table;
  std::memset(&table, 0, sizeof(table));
  table.FindClass = fake_FindClass;
  table.ThrowNew = fake_ThrowNew;
  table.NewGlobalRef = fake_NewGlobalRef;
  table.DeleteGlobalRef = fake_DeleteGlobalRef;
  table.GetObjectClass = fake_GetObjectClass;
  table.GetMethodID = fake_GetMethodID;
  table.CallObjectMethodV = fake_CallObjectMethodV;
  table.CallVoidMethodV = fake_CallVoidMethodV;
  table.NewStringUTF = fake_NewStringUTF;
  table.GetArrayLength = fake_GetArrayLength;
  table.GetByteArrayElements = fake_GetByteArrayElements;
  table.ReleaseByteArrayElements = fake_ReleaseByteArrayElements;
  table.ExceptionCheck = fake_ExceptionCheck;
  JNIEnv_ env{&table};

  JavaVM_ vm{nullptr};
  if (JNI_OnLoad(&vm, nullptr) != JNI_VERSION_1_8) {
    std::fprintf(stderr, "FAIL: JNI_OnLoad version\n");
    return 1;
  }

  jlong ptr = Java_org_blaze_1tpu_JniBridge_callNative(
      &env, FAKE_CLASS, (jlong)1 << 30, (jobject)&wrapper);
  if (!ptr) {
    std::fprintf(stderr, "FAIL: callNative returned 0\n");
    return 1;
  }
  int batches = 0;
  while (Java_org_blaze_1tpu_JniBridge_nextBatch(&env, FAKE_CLASS, ptr) ==
         JNI_TRUE) {
    batches++;
    if (batches > 64) {
      std::fprintf(stderr, "FAIL: runaway batches\n");
      return 1;
    }
  }
  Java_org_blaze_1tpu_JniBridge_finalizeNative(&env, FAKE_CLASS, ptr);

  if (!wrapper.error.empty()) {
    std::fprintf(stderr, "FAIL: error set: %s\n", wrapper.error.c_str());
    return 1;
  }
  std::vector<int64_t> want_y = {11, 12, 0, 14};
  std::vector<uint8_t> want_yv = {1, 1, 0, 1};
  std::vector<std::string> want_u = {"AB", "CD", "", "EF"};
  if (wrapper.y.size() != want_y.size()) {
    std::fprintf(stderr, "FAIL: expected 4 rows, got %zu\n", wrapper.y.size());
    return 1;
  }
  for (size_t i = 0; i < want_y.size(); i++) {
    // null slots carry unspecified payload: compare validity, and
    // values only where valid (same contract as gateway_test.cc)
    if (wrapper.y_valid[i] != want_yv[i] ||
        (want_yv[i] && wrapper.y[i] != want_y[i])) {
      std::fprintf(stderr, "FAIL: y[%zu] = %lld valid=%d\n", i,
                   (long long)wrapper.y[i], wrapper.y_valid[i]);
      return 1;
    }
    if (wrapper.u_valid[i] != want_yv[i] ||
        (want_yv[i] && wrapper.u[i] != want_u[i])) {
      std::fprintf(stderr, "FAIL: u[%zu] mismatch '%s'\n", i,
                   wrapper.u[i].c_str());
      return 1;
    }
  }
  if (wrapper.global_refs != 0) {
    std::fprintf(stderr, "FAIL: leaked %d global refs\n", wrapper.global_refs);
    return 1;
  }
  std::printf("jni_gateway_test OK: %d batches, y+u verified, refs balanced\n",
              batches);
  return 0;
}
