// JDK-free end-to-end gateway test (round-1 VERDICT #3).
//
// Drives the REAL boundary path with no JVM anywhere:
//   TaskDefinition bytes (built by the python serde, ≙ the JVM's
//   BlazeCallNativeWrapper.getRawTaskDefinition)
//     -> bt_gateway_call_native (decode + plan build + producer thread,
//        ≙ exec.rs:46-142 / rt.rs:57-133)
//     -> bt_gateway_next_batch per batch, Arrow C-FFI export crossing
//        the boundary (strings INCLUDED)
//     -> this test imports the arrays back through
//        bt_arrow_import_primitive / bt_arrow_import_string and
//        verifies values, nulls, and the error path.
//
// Run: ctest --test-dir native/build  (or ./gateway_test <repo_root>)

#include <Python.h>

#include <cassert>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "blaze_native.h"

struct Captured {
  std::vector<int64_t> y;
  std::vector<uint8_t> y_valid;
  std::vector<std::string> u;
  std::vector<uint8_t> u_valid;
  std::string error;
};

static void on_import(void* user, uintptr_t addr) {
  auto* cap = (Captured*)user;
  auto* fb = (bt_ffi_batch*)addr;
  assert(fb->n_cols == 2);
  int64_t n = fb->arrays[0].length;

  std::vector<int64_t> data(n);
  std::vector<uint8_t> valid(n);
  int rc = bt_arrow_import_primitive(&fb->schemas[0], &fb->arrays[0],
                                     data.data(), valid.data(), n);
  assert(rc == 0);
  for (int64_t i = 0; i < n; i++) {
    cap->y.push_back(data[i]);
    cap->y_valid.push_back(valid[i]);
  }

  const int32_t W = 8;
  std::vector<uint8_t> sdata((size_t)(n * W));
  std::vector<int32_t> slens(n);
  std::vector<uint8_t> svalid(n);
  rc = bt_arrow_import_string(&fb->schemas[1], &fb->arrays[1], sdata.data(),
                              slens.data(), svalid.data(), n, W);
  assert(rc == 0);
  for (int64_t i = 0; i < n; i++) {
    cap->u.emplace_back((const char*)&sdata[(size_t)(i * W)], (size_t)slens[i]);
    cap->u_valid.push_back(svalid[i]);
  }

  // consumer side of the Arrow contract: release imported arrays
  for (int64_t c = 0; c < fb->n_cols; c++) {
    if (fb->arrays[c].release) fb->arrays[c].release(&fb->arrays[c]);
    if (fb->schemas[c].release) fb->schemas[c].release(&fb->schemas[c]);
  }
}

static void on_error(void* user, const char* msg) {
  ((Captured*)user)->error = msg ? msg : "";
}

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
  // force the CPU backend before the interpreter comes up — a unit
  // test never claims a chip
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
      "td = task_definition(plan, 'ctest', 0, 0)\n";
  PyObject* td = run_py(build_task, "td");
  if (!td || !PyBytes_Check(td)) {
    std::fprintf(stderr, "FAIL: task definition build\n");
    return 1;
  }
  std::string td_bytes(PyBytes_AsString(td), (size_t)PyBytes_Size(td));

  // hand the GIL to the gateway's producer thread
  PyThreadState* ts = PyEval_SaveThread();

  Captured cap;
  bt_gateway_callbacks cbs{&cap, on_import, on_error};
  void* rt = bt_gateway_call_native((const uint8_t*)td_bytes.data(),
                                    (int64_t)td_bytes.size(), &cbs);
  int batches = 0;
  while (true) {
    int32_t rc = bt_gateway_next_batch(rt);
    if (rc == 1) {
      batches++;
      continue;
    }
    if (rc == -1) {
      std::fprintf(stderr, "FAIL: gateway error: %s\n", bt_gateway_last_error(rt));
      return 1;
    }
    break;
  }
  bt_gateway_finalize(rt);

  // ---- verify: y = x + 10, u = upper(s), nulls preserved ------------------
  if (batches < 1 || cap.y.size() != 4) {
    std::fprintf(stderr, "FAIL: expected 4 rows, got %zu\n", cap.y.size());
    return 1;
  }
  const int64_t want_y[4] = {11, 12, 0, 14};
  const uint8_t want_yv[4] = {1, 1, 0, 1};
  const char* want_u[4] = {"AB", "CD", "", "EF"};
  const uint8_t want_uv[4] = {1, 1, 0, 1};
  for (int i = 0; i < 4; i++) {
    if (cap.y_valid[i] != want_yv[i] || (want_yv[i] && cap.y[i] != want_y[i])) {
      std::fprintf(stderr, "FAIL: y[%d] = %lld valid=%d\n", i,
                   (long long)cap.y[i], cap.y_valid[i]);
      return 1;
    }
    if (cap.u_valid[i] != want_uv[i] || (want_uv[i] && cap.u[i] != want_u[i])) {
      std::fprintf(stderr, "FAIL: u[%d] = '%s' valid=%d\n", i, cap.u[i].c_str(),
                   cap.u_valid[i]);
      return 1;
    }
  }

  // ---- error path: malformed TaskDefinition surfaces via set_error --------
  Captured bad;
  bt_gateway_callbacks bad_cbs{&bad, on_import, on_error};
  const uint8_t junk[] = {0xde, 0xad, 0xbe, 0xef, 0x42};
  void* rt2 = bt_gateway_call_native(junk, sizeof(junk), &bad_cbs);
  int32_t rc2 = bt_gateway_next_batch(rt2);
  if (rc2 != -1 || bad.error.empty()) {
    std::fprintf(stderr, "FAIL: error path rc=%d err='%s'\n", rc2,
                 bad.error.c_str());
    return 1;
  }
  bt_gateway_finalize(rt2);

  PyEval_RestoreThread(ts);
  std::printf("gateway_test OK: %d batch(es), 4 rows, strings + nulls + error path\n",
              batches);
  return 0;
}
