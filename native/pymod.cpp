// CPython extension front-end for the native encoder.
//
// Adds a direct PyObject-walk encode path: resolves selectors over the
// Authorization-JSON dicts in place (no json.dumps → parse round-trip),
// renders with the same gjson-String semantics, and scatters into the numpy
// buffers.  Holds the GIL (it touches Python objects); the JSON-blob path in
// encoder.cpp stays available for GIL-free multithreaded encoding on
// many-core hosts.  Both share Policy/Interner/render/leaf-pass code — this
// file #includes encoder.cpp as a single translation unit.
//
// Build (one shared object, importable AND ctypes-loadable):
//   g++ -O2 -std=c++17 -shared -fPIC -pthread -I$(python-include) \
//       pymod.cpp -o _atpuenc.so

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include "encoder.cpp"
#include "frontend.cpp"
#include "verdict_cache.cpp"

namespace {

PyObject* g_json_dumps = nullptr;   // json.dumps
PyObject* g_dumps_kwargs = nullptr; // {"separators": (",", ":"), "ensure_ascii": False}

void policy_capsule_free(PyObject* cap) {
  Policy* p = (Policy*)PyCapsule_GetPointer(cap, "atpu.Policy");
  delete p;
}

// render a Python value with compiler/encode.py::_render semantics.
// returns false if a Python error occurred (non-serializable nested value).
bool render_py(PyObject* v, std::string& out) {
  if (v == nullptr || v == Py_None) return true;  // ""
  if (PyUnicode_Check(v)) {
    Py_ssize_t n;
    const char* s = PyUnicode_AsUTF8AndSize(v, &n);
    if (s == nullptr) return false;
    out.append(s, (size_t)n);
    return true;
  }
  if (PyBool_Check(v)) {  // before PyLong: bool subclasses int
    out += (v == Py_True) ? "true" : "false";
    return true;
  }
  if (PyLong_Check(v)) {
    int overflow_flag = 0;
    long long ll = PyLong_AsLongLongAndOverflow(v, &overflow_flag);
    if (!overflow_flag && !(ll == -1 && PyErr_Occurred())) {
      char buf[32];
      auto res = std::to_chars(buf, buf + sizeof buf, ll);
      out.append(buf, res.ptr - buf);
      return true;
    }
    PyErr_Clear();
    PyObject* s = PyObject_Str(v);  // big ints
    if (s == nullptr) return false;
    Py_ssize_t n;
    const char* cs = PyUnicode_AsUTF8AndSize(s, &n);
    if (cs == nullptr) { Py_DECREF(s); return false; }
    out.append(cs, (size_t)n);
    Py_DECREF(s);
    return true;
  }
  if (PyFloat_Check(v)) {
    num_str(PyFloat_AS_DOUBLE(v), out);
    return true;
  }
  // dict/list/other → compact raw JSON via the real json.dumps (exact parity
  // with authjson.selector.to_raw_json by construction)
  PyObject* args = PyTuple_Pack(1, v);
  if (args == nullptr) return false;
  PyObject* s = PyObject_Call(g_json_dumps, args, g_dumps_kwargs);
  Py_DECREF(args);
  if (s == nullptr) return false;
  Py_ssize_t n;
  const char* cs = PyUnicode_AsUTF8AndSize(s, &n);
  if (cs == nullptr) { Py_DECREF(s); return false; }
  out.append(cs, (size_t)n);
  Py_DECREF(s);
  return true;
}

// walk a plain dot-path over Python dicts/lists; returns borrowed ref or
// nullptr for missing.  seg_objs are pre-built PyUnicode keys (hash cached).
PyObject* walk_py(PyObject* doc, const Policy* p, PyObject* seg_objs, int32_t attr) {
  PyObject* cur = doc;
  for (int32_t s = p->attr_seg_offs[attr]; s < p->attr_seg_offs[attr + 1]; ++s) {
    if (cur == nullptr) return nullptr;
    if (PyDict_Check(cur)) {
      cur = PyDict_GetItem(cur, PyTuple_GET_ITEM(seg_objs, s));  // borrowed
    } else if (PyList_Check(cur)) {
      const char* kp = p->strings.data() + p->seg_views[s].first;
      int32_t klen = p->seg_views[s].second;
      const char* q = kp; const char* qe = kp + klen;
      while (q < qe && (*q == ' ' || *q == '\t')) ++q;
      while (qe > q && (qe[-1] == ' ' || qe[-1] == '\t')) --qe;
      bool neg = false;
      if (q < qe && (*q == '+' || *q == '-')) { neg = (*q == '-'); ++q; }
      if (q == qe) return nullptr;
      Py_ssize_t len = PyList_GET_SIZE(cur);
      int64_t idx = 0;
      for (; q < qe; ++q) {
        if (*q < '0' || *q > '9') return nullptr;
        idx = idx * 10 + (*q - '0');
        if (idx > len) break;
      }
      if (neg || idx >= len || q != qe) {
        // re-check: digits ran clean only if q reached qe
        if (q != qe) return nullptr;
        return nullptr;
      }
      cur = PyList_GET_ITEM(cur, (Py_ssize_t)idx);
    } else {
      return nullptr;
    }
  }
  return cur;
}

// encode_docs(policy_capsule, seg_objs, docs, rows_addr, n_docs,
//             A, K, L, NB, DVB,
//             attrs_val, attrs_members, overflow, cpu_lane, attr_bytes, byte_ovf,
//             task_r, task_leaf, task_val_off, task_val_len, max_tasks,
//             arena_addr, arena_cap, elem16)
//             (all *_addr are numpy .ctypes.data ints; elem16: id buffers
//              are int16 when the interner fits — see pack.wire_dtype)
PyObject* encode_docs(PyObject*, PyObject* args) {
  PyObject* cap; PyObject* seg_objs; PyObject* docs;
  unsigned long long rows_a, av_a, am_a, ov_a, cl_a, ab_a, bo_a;
  unsigned long long tr_a, tl_a, to_a, tv_a, arena_a;
  int n_docs, A, K, L, NB, DVB, max_tasks, elem16;
  long long arena_cap;
  if (!PyArg_ParseTuple(
          args, "OOOKiiiiiiKKKKKKKKKKiKLi",
          &cap, &seg_objs, &docs, &rows_a, &n_docs, &A, &K, &L, &NB, &DVB,
          &av_a, &am_a, &ov_a, &cl_a, &ab_a, &bo_a,
          &tr_a, &tl_a, &to_a, &tv_a, &max_tasks, &arena_a, &arena_cap,
          &elem16))
    return nullptr;
  Policy* p = (Policy*)PyCapsule_GetPointer(cap, "atpu.Policy");
  if (p == nullptr) return nullptr;
  const int32_t* rows = (const int32_t*)rows_a;
  void* attrs_val = (void*)av_a;
  void* attrs_members = (void*)am_a;
  uint8_t* overflow = (uint8_t*)ov_a;
  uint8_t* cpu_lane = (uint8_t*)cl_a;
  uint8_t* attr_bytes = (uint8_t*)ab_a;
  uint8_t* byte_ovf = (uint8_t*)bo_a;

  std::vector<int32_t> attr_epoch((size_t)A, -1);
  std::vector<std::string> attr_rendered((size_t)A);
  std::vector<std::vector<int32_t>> attr_elem_ids((size_t)A);
  std::vector<Task> tasks;
  std::string tmp;

  for (int32_t r = 0; r < n_docs; ++r) {
    PyObject* doc = PyList_GET_ITEM(docs, r);
    int32_t row = rows[r];
    for (int32_t ai = p->cfg_attr_offs[row]; ai < p->cfg_attr_offs[row + 1]; ++ai) {
      int32_t attr = p->cfg_attr_idx[ai];
      if (p->attr_complex[attr]) continue;
      PyObject* v = walk_py(doc, p, seg_objs, attr);
      attr_epoch[attr] = r;
      std::string& rendered = attr_rendered[attr];
      rendered.clear();
      if (!render_py(v, rendered)) return nullptr;
      int32_t vid = p->interner.lookup(rendered.data(), rendered.size());
      store_id(attrs_val, (int64_t)r * A + attr, vid, elem16);
      int32_t slot = p->attr_byte_slot[attr];
      if (slot >= 0) {
        if ((int64_t)rendered.size() > p->cfg_byte_width[row] ||
            memchr(rendered.data(), 0, rendered.size()) != nullptr) {
          byte_ovf[(int64_t)r * NB + slot] = 1;
        } else if (!rendered.empty()) {
          memcpy(attr_bytes + ((int64_t)r * NB + slot) * DVB, rendered.data(),
                 rendered.size());
        }
      }
      std::vector<int32_t>& elems = attr_elem_ids[attr];
      elems.clear();
      if (v != nullptr && PyList_Check(v)) {
        Py_ssize_t n = PyList_GET_SIZE(v);
        for (Py_ssize_t k = 0; k < n; ++k) {
          tmp.clear();
          if (!render_py(PyList_GET_ITEM(v, k), tmp)) return nullptr;
          int32_t eid = p->interner.lookup(tmp.data(), tmp.size());
          elems.push_back(eid);
          if (k < K) store_id(attrs_members, ((int64_t)r * A + attr) * K + k, eid, elem16);
        }
        if ((int64_t)n > K) overflow[(int64_t)r * A + attr] = 1;
      } else if (v != nullptr && v != Py_None) {
        store_id(attrs_members, ((int64_t)r * A + attr) * K, vid, elem16);
        elems.push_back(vid);
      }
    }
    process_cpu_leaves(p, r, row, attr_epoch, attr_rendered, attr_elem_ids,
                       A, L, NB, byte_ovf, overflow, cpu_lane, tasks);
  }

  int64_t n_tasks = merge_tasks(&tasks, 1, (int32_t*)tr_a, (int32_t*)tl_a,
                                (int64_t*)to_a, (int32_t*)tv_a, max_tasks,
                                (char*)arena_a, arena_cap);
  return PyLong_FromLongLong(n_tasks);
}

// policy_new_py(intern_blob, intern_offs_addr, intern_ids_addr, n_intern,
//               n_attrs, seg_blob, seg_offs_addr, n_segs, attr_seg_offs_addr,
//               attr_complex_addr, attr_byte_slot_addr,
//               n_leaves, leaf_op_addr, leaf_attr_addr, leaf_const_addr,
//               n_configs, cfg_attr_offs_addr, cfg_attr_idx_addr,
//               cfg_cpu_offs_addr, cfg_cpu_idx_addr, members_k,
//               cfg_byte_width_addr, nb)
PyObject* policy_new_py(PyObject*, PyObject* args) {
  Py_buffer intern_blob, seg_blob;
  unsigned long long io_a, ii_a, so_a, aso_a, ac_a, abs_a;
  unsigned long long lo_a, la_a, lc_a, cao_a, cai_a, cco_a, cci_a, cbw_a;
  int n_intern, n_attrs, n_segs, n_leaves, n_configs, members_k, nb;
  if (!PyArg_ParseTuple(
          args, "y*KKiiy*KiKKKiKKKiKKKKiKi",
          &intern_blob, &io_a, &ii_a, &n_intern,
          &n_attrs, &seg_blob, &so_a, &n_segs, &aso_a, &ac_a, &abs_a,
          &n_leaves, &lo_a, &la_a, &lc_a,
          &n_configs, &cao_a, &cai_a, &cco_a, &cci_a,
          &members_k, &cbw_a, &nb))
    return nullptr;
  Policy* p = atpu_policy_new(
      (const char*)intern_blob.buf, (const int64_t*)io_a, (const int32_t*)ii_a,
      n_intern, n_attrs, (const char*)seg_blob.buf, (const int64_t*)so_a,
      n_segs, (const int32_t*)aso_a, (const uint8_t*)ac_a, (const int32_t*)abs_a,
      n_leaves, (const int32_t*)lo_a, (const int32_t*)la_a, (const int32_t*)lc_a,
      n_configs, (const int32_t*)cao_a, (const int32_t*)cai_a,
      (const int32_t*)cco_a, (const int32_t*)cci_a, members_k,
      (const int32_t*)cbw_a, nb);
  PyBuffer_Release(&intern_blob);
  PyBuffer_Release(&seg_blob);
  return PyCapsule_New(p, "atpu.Policy", policy_capsule_free);
}

// encode_json_py(policy_capsule, blob, doc_offs_addr, n_docs, rows_addr,
//                A, K, L, NB, DVB, <6 out addrs>, <4 task addrs>, max_tasks,
//                arena_addr, arena_cap, n_threads, elem16)
// GIL released around the C encode (threaded path for many-core hosts).
PyObject* encode_json_py(PyObject*, PyObject* args) {
  PyObject* cap; Py_buffer blob;
  unsigned long long do_a, rows_a, av_a, am_a, ov_a, cl_a, ab_a, bo_a;
  unsigned long long tr_a, tl_a, to_a, tv_a, arena_a;
  int n_docs, A, K, L, NB, DVB, max_tasks, n_threads, elem16;
  long long arena_cap;
  if (!PyArg_ParseTuple(
          args, "Oy*KiKiiiiiKKKKKKKKKKiKLii",
          &cap, &blob, &do_a, &n_docs, &rows_a, &A, &K, &L, &NB, &DVB,
          &av_a, &am_a, &ov_a, &cl_a, &ab_a, &bo_a,
          &tr_a, &tl_a, &to_a, &tv_a, &max_tasks, &arena_a, &arena_cap,
          &n_threads, &elem16))
    return nullptr;
  Policy* p = (Policy*)PyCapsule_GetPointer(cap, "atpu.Policy");
  if (p == nullptr) { PyBuffer_Release(&blob); return nullptr; }
  int64_t rc;
  Py_BEGIN_ALLOW_THREADS
  rc = atpu_encode(p, (const char*)blob.buf, (const int64_t*)do_a, n_docs,
                   (const int32_t*)rows_a, A, K, L, NB, DVB,
                   (void*)av_a, (void*)am_a, (uint8_t*)ov_a,
                   (uint8_t*)cl_a, (uint8_t*)ab_a, (uint8_t*)bo_a,
                   (int32_t*)tr_a, (int32_t*)tl_a, (int64_t*)to_a,
                   (int32_t*)tv_a, max_tasks, (char*)arena_a, arena_cap,
                   n_threads, elem16);
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&blob);
  return PyLong_FromLongLong(rc);
}

// ---------------------------------------------------------------------------
// native gRPC frontend (native/frontend.cpp)
// ---------------------------------------------------------------------------

static long dict_int(PyObject* d, const char* k, long dflt = 0) {
  PyObject* v = PyDict_GetItemString(d, k);
  return v ? PyLong_AsLong(v) : dflt;
}

static unsigned long long dict_addr(PyObject* d, const char* k) {
  PyObject* v = PyDict_GetItemString(d, k);
  return v ? PyLong_AsUnsignedLongLong(v) : 0;
}

static bool dict_bytes(PyObject* d, const char* k, std::string& out) {
  PyObject* v = PyDict_GetItemString(d, k);
  if (v == nullptr || !PyBytes_Check(v)) return false;
  out.assign(PyBytes_AS_STRING(v), (size_t)PyBytes_GET_SIZE(v));
  return true;
}

static bool dict_str(PyObject* d, const char* k, std::string& out) {
  PyObject* v = PyDict_GetItemString(d, k);
  if (v == nullptr || !PyUnicode_Check(v)) return false;
  Py_ssize_t n;
  const char* s = PyUnicode_AsUTF8AndSize(v, &n);
  if (s == nullptr) return false;
  out.assign(s, (size_t)n);
  return true;
}

// plan tuple list (runtime/native_frontend.py plan format) → FastPlan vector
static bool parse_plans(PyObject* plans, std::vector<fe::FastPlan>& out,
                        bool* needs_split) {
  for (Py_ssize_t j = 0; plans != nullptr && j < PyList_GET_SIZE(plans); ++j) {
    PyObject* t = PyList_GET_ITEM(plans, j);
    fe::FastPlan pl;
    pl.attr = (int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 0));
    pl.kind = (int)PyLong_AsLong(PyTuple_GET_ITEM(t, 1));
    Py_ssize_t kn;
    const char* ks = PyUnicode_AsUTF8AndSize(PyTuple_GET_ITEM(t, 2), &kn);
    if (ks == nullptr) return false;
    pl.key.assign(ks, (size_t)kn);
    pl.const_vid = (int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 3));
    pl.const_missing = PyObject_IsTrue(PyTuple_GET_ITEM(t, 4)) == 1;
    PyObject* mems = PyTuple_GET_ITEM(t, 5);
    for (Py_ssize_t m = 0; m < PyList_GET_SIZE(mems); ++m)
      pl.const_members.push_back((int32_t)PyLong_AsLong(PyList_GET_ITEM(mems, m)));
    PyObject* cb = PyTuple_GET_ITEM(t, 6);
    pl.const_bytes.assign(PyBytes_AS_STRING(cb), (size_t)PyBytes_GET_SIZE(cb));
    pl.const_byte_ovf = PyObject_IsTrue(PyTuple_GET_ITEM(t, 7)) == 1;
    if (needs_split && (pl.kind == fe::K_URL_PATH || pl.kind == fe::K_QUERY))
      *needs_split = true;
    out.push_back(std::move(pl));
  }
  return true;
}

// fe_start(port, bmax, nslots, window_us, slow_cap, health_bytes, any_addr) -> 0
PyObject* fe_start_py(PyObject*, PyObject* args) {
  int port, bmax, nslots, any_addr = 0;
  long window_us, slow_cap;
  Py_buffer health;
  if (!PyArg_ParseTuple(args, "iiilly*|i", &port, &bmax, &nslots, &window_us,
                        &slow_cap, &health, &any_addr))
    return nullptr;
  if (fe::g_srv != nullptr) {
    PyBuffer_Release(&health);
    PyErr_SetString(PyExc_RuntimeError, "frontend already started");
    return nullptr;
  }
  fe::Server* S = new fe::Server();
  S->port = port;
  S->any_addr = any_addr != 0;
  S->bmax = bmax;
  S->nslots = nslots;
  S->window_us = window_us;
  S->slow_cap = (size_t)slow_cap;
  S->health_msg.assign((const char*)health.buf, (size_t)health.len);
  PyBuffer_Release(&health);
  int rc = fe::server_start(S);
  if (rc != 0) {
    delete S;
    return PyLong_FromLong(rc);
  }
  fe::g_srv = S;
  return PyLong_FromLong(0);
}

PyObject* fe_port_py(PyObject*, PyObject*) {
  return PyLong_FromLong(fe::g_srv ? fe::g_srv->bound_port : -1);
}

PyObject* fe_stop_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  if (S != nullptr) {
    Py_BEGIN_ALLOW_THREADS
    fe::server_stop(S);
    Py_END_ALLOW_THREADS
    fe::g_srv = nullptr;
    // leak the Server struct intentionally: Python threads may still be
    // inside fe_wait_* draining the final STOPPED event
  }
  Py_RETURN_NONE;
}

// fe_swap(spec_dict) -> 0; spec described in runtime/native_frontend.py
PyObject* fe_swap_py(PyObject*, PyObject* args) {
  PyObject* d;
  if (!PyArg_ParseTuple(args, "O!", &PyDict_Type, &d)) return nullptr;
  fe::Server* S = fe::g_srv;
  if (S == nullptr) {
    PyErr_SetString(PyExc_RuntimeError, "frontend not started");
    return nullptr;
  }
  auto snap = std::make_shared<fe::Snapshot>();
  snap->id = dict_int(d, "snap_id");
  PyObject* cap = PyDict_GetItemString(d, "policy");
  if (cap != nullptr && cap != Py_None) {
    Policy* p = (Policy*)PyCapsule_GetPointer(cap, "atpu.Policy");
    if (p == nullptr) return nullptr;
    snap->interner = &p->interner;
  }
  snap->A = (int)dict_int(d, "A");
  snap->M = (int)dict_int(d, "M");
  snap->K = (int)dict_int(d, "K");
  snap->C = (int)dict_int(d, "C");
  snap->NB = (int)dict_int(d, "NB");
  snap->DVB = (int)dict_int(d, "DVB");
  snap->elem16 = dict_int(d, "elem16") != 0;
  snap->trace_every = dict_int(d, "trace_every", 0);
  snap->S = (int)dict_int(d, "S", 1);
  if (snap->S < 1) snap->S = 1;
  const long SA = (long)snap->S * snap->A;
  const int32_t* ams = (const int32_t*)dict_addr(d, "attr_member_slot_addr");
  const int32_t* abs_v = (const int32_t*)dict_addr(d, "attr_byte_slot_addr");
  if (SA > 0 && ams != nullptr)
    snap->attr_member_slot.assign(ams, ams + SA);
  if (SA > 0 && abs_v != nullptr)
    snap->attr_byte_slot_v.assign(abs_v, abs_v + SA);
  snap->attr_member_slot.resize(SA, -1);
  snap->attr_byte_slot_v.resize(SA, -1);
  // dfa_R counts TOTAL stacked rows (S*R for sharded corpora); cfg_dfas
  // rows arrive globalized by the Python side
  long dfa_R = dict_int(d, "dfa_R");
  snap->dfa_S = (int)dict_int(d, "dfa_S");
  snap->dfa_state_bytes = dict_int(d, "dfa_state_bytes", 1) == 2 ? 2 : 1;
  if (dfa_R > 0 && snap->dfa_S > 0) {
    const uint8_t* tr = (const uint8_t*)dict_addr(d, "dfa_trans_addr");
    const uint8_t* fl = (const uint8_t*)dict_addr(d, "dfa_flags_addr");
    snap->dfa_trans.assign(
        tr, tr + (size_t)dfa_R * snap->dfa_S * 256 * snap->dfa_state_bytes);
    snap->dfa_flags.assign(fl, fl + (size_t)dfa_R * snap->dfa_S);
  }
  snap->G = (int)dict_int(d, "G", 0);
  snap->cfg_dfas.resize((size_t)snap->S * snap->G);
  PyObject* cdfas = PyDict_GetItemString(d, "cfg_dfas");
  if (cdfas != nullptr) {
    for (Py_ssize_t g = 0; g < PyList_GET_SIZE(cdfas) &&
                           g < (Py_ssize_t)snap->cfg_dfas.size(); ++g) {
      PyObject* lst = PyList_GET_ITEM(cdfas, g);
      for (Py_ssize_t j = 0; j < PyList_GET_SIZE(lst); ++j) {
        PyObject* t = PyList_GET_ITEM(lst, j);
        snap->cfg_dfas[g].push_back(
            {(int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 0)),
             (int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 1)),
             (int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 2))});
      }
    }
  }
  size_t most_dfas = 0;
  for (const auto& refs : snap->cfg_dfas) most_dfas = std::max(most_dfas, refs.size());
  snap->scan_lanes.resize(most_dfas);
  // DFAs a config has on each byte slot: what a value's bytes are counted by
  const size_t NBs = (size_t)std::max(snap->NB, 1);
  snap->cfg_slot_dfas.assign(snap->cfg_dfas.size() * NBs, 0);
  for (size_t ci = 0; ci < snap->cfg_dfas.size(); ++ci) {
    const size_t meta0 = snap->G > 0 ? (ci / (size_t)snap->G) * (size_t)snap->A : 0;
    for (const fe::DfaRef& r : snap->cfg_dfas[ci]) {
      const size_t at = meta0 + (size_t)r.attr;
      const int32_t bslot = at < snap->attr_byte_slot_v.size() ? snap->attr_byte_slot_v[at] : -1;
      if (bslot >= 0 && (size_t)bslot < NBs) snap->cfg_slot_dfas[ci * NBs + bslot]++;
    }
  }
  if (!dict_bytes(d, "invalid", snap->invalid_msg) ||
      !dict_bytes(d, "notfound", snap->notfound_msg) ||
      !dict_bytes(d, "health", snap->health_msg)) {
    PyErr_SetString(PyExc_ValueError, "swap spec missing response templates");
    return nullptr;
  }
  PyObject* fcs = PyDict_GetItemString(d, "fcs");
  for (Py_ssize_t i = 0; fcs != nullptr && i < PyList_GET_SIZE(fcs); ++i) {
    PyObject* f = PyList_GET_ITEM(fcs, i);
    fe::FastConfig fc;
    fc.row = (int32_t)dict_int(f, "row");
    fc.shard = (int32_t)dict_int(f, "shard", 0);
    fc.dvb = (int32_t)dict_int(f, "dvb", snap->DVB);
    fc.has_batch = dict_int(f, "has_batch", 1) != 0;
    fc.hybrid = dict_int(f, "hybrid", 0) != 0;
    dict_bytes(f, "ok", fc.ok_msg);
    dict_bytes(f, "deny", fc.deny_msg);
    if (!parse_plans(PyDict_GetItemString(f, "plans"), fc.plans, &fc.needs_split))
      return nullptr;
    dict_str(f, "ns", fc.ns);
    dict_str(f, "name", fc.name);
    PyObject* srcs = PyDict_GetItemString(f, "sources");
    for (Py_ssize_t j = 0; srcs != nullptr && j < PyList_GET_SIZE(srcs); ++j) {
      PyObject* sd = PyList_GET_ITEM(srcs, j);
      fe::CredSource src;
      src.cred_kind = (int)dict_int(sd, "cred_kind", 0);
      src.dyn = dict_int(sd, "dyn", 0) != 0;
      dict_str(sd, "cred_key", src.cred_key);
      PyObject* vars = PyDict_GetItemString(sd, "variants");
      for (Py_ssize_t k = 0; vars != nullptr && k < PyList_GET_SIZE(vars); ++k) {
        // (key_bytes, plans, ok_bytes, deny_bytes) — empty = config default
        PyObject* kv = PyList_GET_ITEM(vars, k);
        PyObject* kb = PyTuple_GET_ITEM(kv, 0);
        PyObject* okb = PyTuple_GET_SIZE(kv) > 2 ? PyTuple_GET_ITEM(kv, 2) : nullptr;
        PyObject* dnb = PyTuple_GET_SIZE(kv) > 3 ? PyTuple_GET_ITEM(kv, 3) : nullptr;
        if (!PyBytes_Check(kb) || (okb != nullptr && !PyBytes_Check(okb)) ||
            (dnb != nullptr && !PyBytes_Check(dnb))) {
          PyErr_SetString(PyExc_TypeError, "variant key/ok/deny must be bytes");
          return nullptr;
        }
        std::vector<fe::FastPlan> vp;
        if (!parse_plans(PyTuple_GET_ITEM(kv, 1), vp, nullptr)) return nullptr;
        int32_t vid = (int32_t)src.var_plans.size();
        src.var_plans.push_back(std::move(vp));
        int32_t ok_idx = -1;
        if (okb != nullptr && PyBytes_GET_SIZE(okb) > 0) {
          ok_idx = (int32_t)src.var_oks.size();
          src.var_oks.emplace_back(PyBytes_AS_STRING(okb),
                                   (size_t)PyBytes_GET_SIZE(okb));
        }
        int32_t deny_idx = -1;
        if (dnb != nullptr && PyBytes_GET_SIZE(dnb) > 0) {
          deny_idx = (int32_t)src.var_denies.size();
          src.var_denies.emplace_back(PyBytes_AS_STRING(dnb),
                                      (size_t)PyBytes_GET_SIZE(dnb));
        }
        src.variants[std::string(PyBytes_AS_STRING(kb),
                                 (size_t)PyBytes_GET_SIZE(kb))] = {
            vid, INT64_MAX, ok_idx, deny_idx};
      }
      fc.sources.push_back(std::move(src));
    }
    PyObject* umsgs = PyDict_GetItemString(f, "unauth_msgs");
    for (Py_ssize_t j = 0; umsgs != nullptr && j < PyList_GET_SIZE(umsgs); ++j) {
      PyObject* b = PyList_GET_ITEM(umsgs, j);
      if (!PyBytes_Check(b)) {
        PyErr_SetString(PyExc_TypeError, "unauth template must be bytes");
        return nullptr;
      }
      fc.unauth_msgs.emplace_back(PyBytes_AS_STRING(b),
                                  (size_t)PyBytes_GET_SIZE(b));
    }
    if (!fc.sources.empty()) {
      size_t n_static = 0;
      for (const auto& s : fc.sources) n_static += s.dyn ? 0 : 1;
      if (fc.unauth_msgs.size() != ((size_t)1 << n_static)) {
        PyErr_SetString(PyExc_ValueError,
                        "unauth_msgs must cover every static-extraction mask");
        return nullptr;
      }
    }
    snap->fcs.push_back(std::move(fc));
  }
  snap->fc_counts.reset(new std::atomic<uint64_t>[snap->fcs.size() * 3 + 1]());
  snap->fc_durs.reset(
      new std::atomic<uint64_t>[snap->fcs.size() * fe::DUR_STRIDE + 1]());
  PyObject* hosts = PyDict_GetItemString(d, "hosts");
  for (Py_ssize_t i = 0; hosts != nullptr && i < PyList_GET_SIZE(hosts); ++i) {
    PyObject* t = PyList_GET_ITEM(hosts, i);
    Py_ssize_t hn;
    const char* hs = PyUnicode_AsUTF8AndSize(PyTuple_GET_ITEM(t, 0), &hn);
    if (hs == nullptr) return nullptr;
    snap->host_map[std::string(hs, (size_t)hn)] =
        (int32_t)PyLong_AsLong(PyTuple_GET_ITEM(t, 1));
  }
  PyObject* slots = PyDict_GetItemString(d, "slots");
  for (Py_ssize_t i = 0; slots != nullptr && i < PyList_GET_SIZE(slots); ++i) {
    PyObject* s = PyList_GET_ITEM(slots, i);
    fe::Slot sl;
    sl.attrs_val = (char*)dict_addr(s, "attrs_val");
    sl.members = (char*)dict_addr(s, "members");
    sl.cpu_dense = (uint8_t*)dict_addr(s, "cpu_dense");
    sl.config_id = (int32_t*)dict_addr(s, "config_id");
    sl.shard_of = (int32_t*)dict_addr(s, "shard_of");
    sl.attr_bytes = (uint8_t*)dict_addr(s, "attr_bytes");
    sl.byte_ovf = (uint8_t*)dict_addr(s, "byte_ovf");
    sl.byte_used = (uint16_t*)dict_addr(s, "byte_used");
    sl.dfa_bytes = (uint32_t*)dict_addr(s, "dfa_bytes");
    snap->slots.push_back(sl);
    snap->free_slots.push_back((int)i);
  }
  snap->slot_entries.resize(snap->slots.size());
  snap->slot_count.resize(snap->slots.size(), 0);
  snap->slot_flush_ns.resize(snap->slots.size(), 0);
  snap->slot_first_ns.resize(snap->slots.size(), 0);

  std::vector<int64_t> retired;
  {
    std::lock_guard<std::mutex> lk(S->mu);
    S->snaps[snap->id] = snap;
    S->cur = snap;
    fe::maybe_retire_locked(S, retired);
  }
  fe::emit_retired(S, retired);
  return PyLong_FromLong(0);
}

// fe_wait_batch(timeout_ms) -> (kind, a, b, c, d, e, f)
PyObject* fe_wait_batch_py(PyObject*, PyObject* args) {
  long timeout_ms;
  if (!PyArg_ParseTuple(args, "l", &timeout_ms)) return nullptr;
  fe::Server* S = fe::g_srv;
  if (S == nullptr)
    return Py_BuildValue("(iLLLLLL)", (int)fe::EV_STOPPED, 0LL, 0LL, 0LL, 0LL, 0LL, 0LL);
  fe::Event ev = {fe::EV_TIMEOUT, 0, 0, 0, 0, 0, 0};
  Py_BEGIN_ALLOW_THREADS {
    std::unique_lock<std::mutex> lk(S->batch_mu);
    if (S->batch_events.empty())
      S->batch_cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                           [&] { return !S->batch_events.empty(); });
    if (!S->batch_events.empty()) {
      ev = S->batch_events.front();
      S->batch_events.pop_front();
    }
  }
  Py_END_ALLOW_THREADS
  return Py_BuildValue("(iLLLLLL)", ev.kind, (long long)ev.a, (long long)ev.b,
                       (long long)ev.c, (long long)ev.d, (long long)ev.e,
                       (long long)ev.f);
}

// fe_take_slow(timeout_ms, max_n) -> list[(req_id, bytes)]
PyObject* fe_take_slow_py(PyObject*, PyObject* args) {
  long timeout_ms;
  int max_n;
  if (!PyArg_ParseTuple(args, "li", &timeout_ms, &max_n)) return nullptr;
  fe::Server* S = fe::g_srv;
  if (S == nullptr) return PyList_New(0);
  std::vector<fe::SlowReq> reqs;
  Py_BEGIN_ALLOW_THREADS {
    std::unique_lock<std::mutex> lk(S->slow_mu);
    if (S->slow_q.empty())
      S->slow_cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                          [&] { return !S->slow_q.empty() || !S->running.load(); });
    while (!S->slow_q.empty() && (int)reqs.size() < max_n) {
      reqs.push_back(std::move(S->slow_q.front()));
      S->slow_q.pop_front();
    }
  }
  Py_END_ALLOW_THREADS
  PyObject* out = PyList_New((Py_ssize_t)reqs.size());
  for (size_t i = 0; i < reqs.size(); ++i) {
    PyObject* b = PyBytes_FromStringAndSize(reqs[i].bytes.data(),
                                            (Py_ssize_t)reqs[i].bytes.size());
    PyList_SET_ITEM(out, (Py_ssize_t)i,
                    Py_BuildValue("(KN)", (unsigned long long)reqs[i].id, b));
  }
  return out;
}

// fe_complete_batch(snap_id, slot, verdict_addr)
PyObject* fe_complete_batch_py(PyObject*, PyObject* args) {
  long long snap_id;
  int slot;
  unsigned long long verdict_a;
  if (!PyArg_ParseTuple(args, "LiK", &snap_id, &slot, &verdict_a)) return nullptr;
  fe::Server* S = fe::g_srv;
  if (S != nullptr) {
    Py_BEGIN_ALLOW_THREADS
    fe::complete_batch(S, snap_id, slot, (const uint8_t*)verdict_a);
    Py_END_ALLOW_THREADS
  }
  Py_RETURN_NONE;
}

// fe_complete_slow(req_id, resp_bytes, grpc_status)
PyObject* fe_complete_slow_py(PyObject*, PyObject* args) {
  unsigned long long req_id;
  Py_buffer resp;
  int grpc_status;
  if (!PyArg_ParseTuple(args, "Ky*i", &req_id, &resp, &grpc_status)) return nullptr;
  fe::Server* S = fe::g_srv;
  if (S != nullptr) {
    // complete_slow contends on the server mutex with the epoll thread —
    // release the GIL so that wait never blocks the Python slow lane
    Py_BEGIN_ALLOW_THREADS
    fe::complete_slow(S, req_id, (const char*)resp.buf, (size_t)resp.len, grpc_status);
    Py_END_ALLOW_THREADS
  }
  PyBuffer_Release(&resp);
  Py_RETURN_NONE;
}

// fe_complete_slow_many([(req_id, resp_bytes, grpc_status), ...]) — batch
// completion: copies the payloads under the GIL, lands them all in two
// lock rounds with the GIL released
PyObject* fe_complete_slow_many_py(PyObject*, PyObject* args) {
  PyObject* lst;
  if (!PyArg_ParseTuple(args, "O!", &PyList_Type, &lst)) return nullptr;
  std::vector<fe::SlowDone> items;
  items.reserve((size_t)PyList_GET_SIZE(lst));
  for (Py_ssize_t i = 0; i < PyList_GET_SIZE(lst); ++i) {
    PyObject* t = PyList_GET_ITEM(lst, i);
    unsigned long long req_id;
    Py_buffer resp;
    int grpc_status;
    if (!PyArg_ParseTuple(t, "Ky*i", &req_id, &resp, &grpc_status))
      return nullptr;
    items.push_back({req_id, std::string((const char*)resp.buf,
                                         (size_t)resp.len), grpc_status});
    PyBuffer_Release(&resp);
  }
  fe::Server* S = fe::g_srv;
  if (S != nullptr && !items.empty()) {
    Py_BEGIN_ALLOW_THREADS
    fe::complete_slow_many(S, items);
    Py_END_ALLOW_THREADS
  }
  Py_RETURN_NONE;
}

// fe_add_variant(snap_id, fc_idx, src_idx, cred_bytes, plans, ok_bytes,
// deny_bytes, exp_ns) -> bool — register a runtime plan variant
// (verified-credential cache entry) for one identity source; called by the
// slow lane after a successful verification.  Empty ok/deny bytes = the
// config's defaults.
PyObject* fe_add_variant_py(PyObject*, PyObject* args) {
  long long snap_id, exp_ns;
  int fc_idx, src_idx;
  Py_buffer cred, okb, dnb;
  PyObject* plans;
  if (!PyArg_ParseTuple(args, "Liiy*O!y*y*L", &snap_id, &fc_idx, &src_idx,
                        &cred, &PyList_Type, &plans, &okb, &dnb, &exp_ns))
    return nullptr;
  fe::Server* S = fe::g_srv;
  std::vector<fe::FastPlan> vp;
  bool parsed = S != nullptr && parse_plans(plans, vp, nullptr);
  std::string cs((const char*)cred.buf, (size_t)cred.len);
  std::string oks((const char*)okb.buf, (size_t)okb.len);
  std::string dns((const char*)dnb.buf, (size_t)dnb.len);
  PyBuffer_Release(&cred);
  PyBuffer_Release(&okb);
  PyBuffer_Release(&dnb);
  if (S == nullptr) Py_RETURN_FALSE;
  if (!parsed) return nullptr;
  bool ok;
  Py_BEGIN_ALLOW_THREADS
  ok = fe::add_variant(S, snap_id, fc_idx, src_idx, std::move(cs),
                       std::move(vp), std::move(oks), std::move(dns), exp_ns);
  Py_END_ALLOW_THREADS
  return PyBool_FromLong(ok ? 1 : 0);
}

// fe_drain_fc_counts() -> list[(ns, name, ok, unauth_missing, unauth_invalid)]
// — per-authconfig direct decisions since the last drain (the dispatcher
// folds them into the pipeline's Prometheus series)
PyObject* fe_drain_fc_counts_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  PyObject* out = PyList_New(0);
  if (S == nullptr || out == nullptr) return out;
  std::unordered_map<std::string, std::array<uint64_t, 3>> agg;
  Py_BEGIN_ALLOW_THREADS
  fe::drain_fc_counts(S, agg);
  Py_END_ALLOW_THREADS
  for (auto& kv : agg) {
    size_t sep = kv.first.find('\x1f');
    if (sep == std::string::npos) continue;
    PyObject* t = Py_BuildValue(
        "(s#s#KKK)", kv.first.data(), (Py_ssize_t)sep, kv.first.data() + sep + 1,
        (Py_ssize_t)(kv.first.size() - sep - 1),
        (unsigned long long)kv.second[0], (unsigned long long)kv.second[1],
        (unsigned long long)kv.second[2]);
    if (t == nullptr) { Py_DECREF(out); return nullptr; }
    PyList_Append(out, t);
    Py_DECREF(t);
  }
  return out;
}

// fe_drain_durations() -> list[(ns, name, [15 bucket counts], sum_ns)] —
// per-authconfig request-duration histogram increments since the last
// drain; the dispatcher folds them into
// auth_server_authconfig_duration_seconds (same buckets as prometheus
// defaults, non-cumulative per-le counts)
PyObject* fe_drain_durations_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  PyObject* out = PyList_New(0);
  if (S == nullptr || out == nullptr) return out;
  std::unordered_map<std::string, std::array<uint64_t, fe::DUR_STRIDE>> agg;
  Py_BEGIN_ALLOW_THREADS
  fe::drain_durations(S, agg);
  Py_END_ALLOW_THREADS
  for (auto& kv : agg) {
    size_t sep = kv.first.find('\x1f');
    if (sep == std::string::npos) continue;
    PyObject* buckets = PyList_New(fe::N_DUR_BUCKETS);
    if (buckets == nullptr) { Py_DECREF(out); return nullptr; }
    for (int k = 0; k < fe::N_DUR_BUCKETS; ++k)
      PyList_SET_ITEM(buckets, k, PyLong_FromUnsignedLongLong(kv.second[k]));
    PyObject* t = Py_BuildValue(
        "(s#s#NK)", kv.first.data(), (Py_ssize_t)sep, kv.first.data() + sep + 1,
        (Py_ssize_t)(kv.first.size() - sep - 1), buckets,
        (unsigned long long)kv.second[fe::N_DUR_BUCKETS]);
    if (t == nullptr) { Py_DECREF(out); return nullptr; }
    PyList_Append(out, t);
    Py_DECREF(t);
  }
  return out;
}

// fe_stage_hist() -> {"wait": [...], "exec": [...], "respond": [...],
// "bounds_ns": [...], "sum_ns": {stage: ns}} — drains (resets) the on-box
// per-request stage histograms: queue-wait (encode→flush), execute
// (flush→complete), respond (complete→HTTP/2 submit).  sum_ns is exact:
// what the loop clock's req_* rows gained since the last call.
PyObject* fe_stage_hist_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  PyObject* d = PyDict_New();
  if (S == nullptr || d == nullptr) return d;
  PyObject* sums = PyDict_New();
  auto dump = [&](const char* key, std::atomic<uint64_t>* arr, int row) {
    PyObject* l = PyList_New(fe::N_STAGE_BUCKETS);
    for (int i = 0; i < fe::N_STAGE_BUCKETS; ++i)
      PyList_SET_ITEM(l, i, PyLong_FromUnsignedLongLong(arr[i].exchange(0)));
    PyDict_SetItemString(d, key, l);
    Py_DECREF(l);
    uint64_t& last = S->hist_drained[row - fe::ROW_REQ_WAIT];
    const uint64_t now = S->clk.rows[row].sum_ns.load();
    PyObject* o = PyLong_FromUnsignedLongLong(now - last);
    PyDict_SetItemString(sums, key, o);
    Py_DECREF(o);
    last = now;
  };
  dump("wait", S->stage_wait, fe::ROW_REQ_WAIT);
  dump("exec", S->stage_exec, fe::ROW_REQ_EXEC);
  dump("respond", S->stage_respond, fe::ROW_REQ_RESPOND);
  PyDict_SetItemString(d, "sum_ns", sums);
  Py_DECREF(sums);
  PyObject* b = PyList_New(fe::N_STAGE_BUCKETS - 1);
  for (int i = 0; i < fe::N_STAGE_BUCKETS - 1; ++i)
    PyList_SET_ITEM(b, i, PyLong_FromLongLong(fe::STAGE_BOUNDS_NS[i]));
  PyDict_SetItemString(d, "bounds_ns", b);
  Py_DECREF(b);
  return d;
}

// fe_loop_clock() -> {"phases": {phase: {count, sum_ns, max_ns}},
// "rows": {row: {count, sum_ns, max_ns}}, "counters": {name: n},
// "slow_turns": [{wake_mono_ns, idle_ns, busy_ns, events, requests,
// answers}, oldest first], "mark_mono_ns": t} — the epoll thread's loop
// clock (frontend.cpp "The loop clock"), cumulative.  `phases` are the
// thread's own and add up to its wall time up to `mark_mono_ns`, its last
// stamp; `rows` holds what is not a phase of it: `turn` and the three
// per-request `req_*`.
PyObject* fe_loop_clock_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  if (S == nullptr) return PyDict_New();
  fe::LoopClock& clk = S->clk;
  PyObject* phases = PyDict_New();
  PyObject* rest = PyDict_New();
  for (int r = 0; r < fe::N_CLOCK_ROWS; ++r) {
    PyObject* row = Py_BuildValue(
        "{s:K,s:K,s:K}", "count", (unsigned long long)clk.rows[r].count.load(),
        "sum_ns", (unsigned long long)clk.rows[r].sum_ns.load(),
        "max_ns", (unsigned long long)clk.rows[r].max_ns.load());
    PyDict_SetItemString(r < fe::N_LOOP_PHASES ? phases : rest,
                         fe::CLOCK_ROW_NAMES[r], row);
    Py_DECREF(row);
  }
  PyObject* counters = PyDict_New();
  for (int c = 0; c < fe::N_LOOP_COUNTERS; ++c) {
    PyObject* o = PyLong_FromUnsignedLongLong(clk.counters[c].load());
    PyDict_SetItemString(counters, fe::LOOP_COUNTER_NAMES[c], o);
    Py_DECREF(o);
  }
  fe::SlowTurn held[fe::N_SLOW_TURNS];
  uint64_t n_turns;
  {
    std::lock_guard<std::mutex> lk(clk.turns_mu);
    n_turns = clk.n_turns;
    memcpy(held, clk.turns, sizeof held);
  }
  const uint64_t kept = n_turns < (uint64_t)fe::N_SLOW_TURNS ? n_turns : fe::N_SLOW_TURNS;
  PyObject* turns = PyList_New((Py_ssize_t)kept);
  for (uint64_t k = 0; k < kept; ++k) {
    const fe::SlowTurn& t = held[(n_turns - kept + k) % fe::N_SLOW_TURNS];
    PyList_SET_ITEM(turns, (Py_ssize_t)k, Py_BuildValue(
        "{s:L,s:L,s:L,s:L,s:L,s:L}", "wake_mono_ns", (long long)t.wake_mono_ns,
        "idle_ns", (long long)t.idle_ns, "busy_ns", (long long)t.busy_ns,
        "events", (long long)t.events, "requests", (long long)t.requests,
        "answers", (long long)t.answers));
  }
  return Py_BuildValue("{s:N,s:N,s:N,s:N,s:L}", "phases", phases, "rows", rest,
                       "counters", counters, "slow_turns", turns,
                       "mark_mono_ns", (long long)clk.mark.load());
}

PyObject* fe_stats_py(PyObject*, PyObject*) {
  fe::Server* S = fe::g_srv;
  PyObject* d = PyDict_New();
  if (S == nullptr) return d;
  auto put = [&](const char* k, uint64_t v) {
    PyObject* o = PyLong_FromUnsignedLongLong(v);
    PyDict_SetItemString(d, k, o);
    Py_DECREF(o);
  };
  put("fast", S->n_fast.load());
  put("slow", S->n_slow.load());
  put("notfound", S->n_notfound.load());
  put("invalid", S->n_invalid.load());
  put("health", S->n_health.load());
  put("allowed", S->n_allowed.load());
  put("denied", S->n_denied.load());
  put("dfa_overflow", S->n_dfa_ovf.load());
  put("slow_shed", S->n_slow_shed.load());
  put("parse_errors", S->n_parse_err.load());
  put("connections", S->n_conns.load());
  put("unauth", S->n_unauth.load());
  put("direct_ok", S->n_direct_ok.load());
  put("dyn_hit", S->n_dyn_hit.load());
  put("dyn_miss", S->n_dyn_miss.load());
  put("dyn_add", S->n_dyn_add.load());
  put("trace_sampled", S->n_trace_sampled.load());
  put("hybrid", S->n_hybrid.load());
  {
    // live backlog gauges (not counters): queued + in-pipeline slow work
    size_t pending, queued;
    {
      std::lock_guard<std::mutex> lk(S->mu);
      pending = S->slow_pending.size();
    }
    {
      std::lock_guard<std::mutex> lk(S->slow_mu);
      queued = S->slow_q.size();
    }
    put("slow_pending", pending);
    put("slow_queued", queued);
  }
  return d;
}

// ---------------------------------------------------------------------------
// verdict cache + batch dedup (verdict_cache.cpp): one call a cut on each
// side of the launch.  Both release the interpreter lock before the cache's
// mutex is taken, and touch no Python object until they hold it again.
// ---------------------------------------------------------------------------

constexpr const char* VC_CACHE = "atpu.VerdictCache";
constexpr const char* VC_TICKET = "atpu.VerdictTicket";

void vc_cache_free(PyObject* cap) {
  delete (vc::Cache*)PyCapsule_GetPointer(cap, VC_CACHE);
}

// a ticket keeps its cache alive: a batch may complete after the frontend
// that planned it has gone
struct VcTicket {
  vc::Ticket t;
  PyObject* cache_cap = nullptr;
};

void vc_ticket_free(PyObject* cap) {
  VcTicket* t = (VcTicket*)PyCapsule_GetPointer(cap, VC_TICKET);
  if (t == nullptr) return;
  Py_XDECREF(t->cache_cap);
  delete t;
}

// vc_new(max_entries, buckets=0) -> cache
PyObject* vc_new_py(PyObject*, PyObject* args) {
  int max_entries;
  long long buckets = 0;
  if (!PyArg_ParseTuple(args, "i|L", &max_entries, &buckets)) return nullptr;
  if (max_entries < 1 || buckets < 0) {
    PyErr_SetString(PyExc_ValueError, "vc_new: max_entries >= 1, buckets >= 0");
    return nullptr;
  }
  return PyCapsule_New(new vc::Cache(max_entries, buckets), VC_CACHE, vc_cache_free);
}

// vc_plan(cache | None, segments, count, tokens, eligible, dedup)
//   -> (arrays, n_cached, n_miss, n_unique, eligible_misses, ticket | None)
// segments: u64 sextuples (address, bytes a row, rows, sub-rows, their
// stride, address of the rows' used bytes or 0: vc::Seg), one an operand
// array of the slot, in key order; tokens u64[count]; eligible u8[count].  arrays
// is vc::Plan::out as int32 bytes.
PyObject* vc_plan_py(PyObject*, PyObject* args) {
  PyObject* cache_o;
  Py_buffer segs, tokens, eligible;
  int count, dedup;
  if (!PyArg_ParseTuple(args, "Oy*iy*y*p", &cache_o, &segs, &count, &tokens,
                        &eligible, &dedup))
    return nullptr;
  PyObject* result = nullptr;
  VcTicket* ticket = nullptr;
  vc::Cache* cache = nullptr;
  if (cache_o != Py_None) {
    cache = (vc::Cache*)PyCapsule_GetPointer(cache_o, VC_CACHE);
    if (cache == nullptr) goto done;
  }
  {
    bool ok = count >= 0 && segs.len % sizeof(vc::Seg) == 0 &&
              tokens.len >= (Py_ssize_t)(count * sizeof(uint64_t)) && eligible.len >= count;
    for (size_t s = 0; ok && s < segs.len / sizeof(vc::Seg); ++s) {
      const vc::Seg& g = ((const vc::Seg*)segs.buf)[s];
      ok = (uint64_t)count <= g.rows && (!g.used || g.sub * g.sub_stride <= g.bytes);
    }
    if (!ok) {
      PyErr_SetString(PyExc_ValueError, "vc_plan: tokens, eligible or a segment shorter than "
                                        "count, or segments not vc::Seg sextuples");
      goto done;
    }
  }
  {
    vc::Plan plan;
    ticket = new VcTicket();
    Py_BEGIN_ALLOW_THREADS
    vc::plan(cache, (const vc::Seg*)segs.buf, (size_t)segs.len / sizeof(vc::Seg), count,
             (const uint64_t*)tokens.buf, (const uint8_t*)eligible.buf, dedup != 0,
             plan, ticket->t);
    Py_END_ALLOW_THREADS
    PyObject* ticket_o;
    if (cache != nullptr) {
      ticket_o = PyCapsule_New(ticket, VC_TICKET, vc_ticket_free);
      if (ticket_o == nullptr) goto done;
      Py_INCREF(cache_o);
      ticket->cache_cap = cache_o;
      ticket = nullptr;  // the capsule's from here
    } else {
      ticket_o = Py_NewRef(Py_None);
    }
    result = Py_BuildValue("(y#iiiiN)", (const char*)plan.out.data(),
                           (Py_ssize_t)(plan.out.size() * sizeof(int32_t)),
                           plan.n_cached, plan.n_miss, plan.n_unique, plan.elig_miss,
                           ticket_o);
  }
done:
  delete ticket;
  PyBuffer_Release(&segs);
  PyBuffer_Release(&tokens);
  PyBuffer_Release(&eligible);
  return result;
}

// vc_commit(ticket, verdict u8[count], firing i32[count] | None) -> evictions
PyObject* vc_commit_py(PyObject*, PyObject* args) {
  PyObject* ticket_o;
  Py_buffer verdict, firing;
  if (!PyArg_ParseTuple(args, "Oy*z*", &ticket_o, &verdict, &firing)) return nullptr;
  PyObject* result = nullptr;
  VcTicket* ticket = (VcTicket*)PyCapsule_GetPointer(ticket_o, VC_TICKET);
  if (ticket != nullptr) {
    if (verdict.len < ticket->t.count ||
        (firing.buf != nullptr &&
         firing.len < (Py_ssize_t)(ticket->t.count * sizeof(int32_t)))) {
      PyErr_SetString(PyExc_ValueError, "vc_commit: verdict/firing shorter than the cut");
    } else {
      vc::Cache* cache = (vc::Cache*)PyCapsule_GetPointer(ticket->cache_cap, VC_CACHE);
      long long evicted;
      Py_BEGIN_ALLOW_THREADS
      evicted = vc::commit(cache, ticket->t, (const uint8_t*)verdict.buf,
                           (const int32_t*)firing.buf);
      Py_END_ALLOW_THREADS
      result = PyLong_FromLongLong(evicted);
    }
  }
  PyBuffer_Release(&verdict);
  if (firing.buf != nullptr) PyBuffer_Release(&firing);
  return result;
}

// ---------------------------------------------------------------------------
// fe_resolve_cut: a completed single-corpus cut in one call
// ---------------------------------------------------------------------------

// the caller's buffers, held until the call returns (a deque: a Py_buffer
// may point into itself, so none may move)
struct HeldBuffers {
  std::deque<Py_buffer> held;
  ~HeldBuffers() {
    for (Py_buffer& b : held) PyBuffer_Release(&b);
  }
  // an `ndim`-D array of `what` whose items are `itemsize` bytes of a
  // format in `kinds` (native byte order), C-contiguous unless `strided`
  // (then its `strides` say where each item lies); ValueError otherwise
  const Py_buffer* get(PyObject* o, bool writable, int ndim, const char* kinds,
                       Py_ssize_t itemsize, Py_ssize_t itemsize_alt, const char* what,
                       bool strided = false) {
    Py_buffer b;
    int flags = (strided ? PyBUF_STRIDES : PyBUF_C_CONTIGUOUS) | PyBUF_FORMAT |
                (writable ? PyBUF_WRITABLE : 0);
    if (!PyObject_CheckBuffer(o) || PyObject_GetBuffer(o, &b, flags) < 0) {
      PyErr_Clear();
      PyErr_Format(PyExc_ValueError, "fe_resolve_cut: %s is not a%s%s array", what,
                   strided ? "" : " contiguous", writable ? " writable" : "");
      return nullptr;
    }
    held.push_back(b);
    const char* f = b.format != nullptr ? b.format : "B";
    if (*f == '@' || *f == '=' || *f == '<') ++f;
    if (b.ndim != ndim || f[0] == '\0' || f[1] != '\0' || strchr(kinds, f[0]) == nullptr ||
        (b.itemsize != itemsize && b.itemsize != itemsize_alt)) {
      PyErr_Format(PyExc_ValueError, "fe_resolve_cut: %s has the wrong dtype or rank", what);
      return nullptr;
    }
    return &held.back();
  }
};

// every one of the first n indices of a 1-D int32 / int64 buffer in [0, bound)
bool indices_below(const Py_buffer* b, Py_ssize_t n, int64_t bound) {
  for (Py_ssize_t i = 0; i < n; ++i) {
    const int64_t v = b->itemsize == 8 ? ((const int64_t*)b->buf)[i] : ((const int32_t*)b->buf)[i];
    if (v < 0 || v >= bound) return false;
  }
  return true;
}

// fe_resolve_cut(parts, plan | None, count, snap_id, slot, verdict, firing | None)
//   -> evictions
// parts: [(packed u8 [>= n, W] (rows may be strided: the runtime's readback
// can pad them), positions int32/int64 [n] | None, n, E), ...],
// the cut's launches (runtime/native_frontend.py _Launched), none for a cut
// the cache answered whole; plan: its CutPlan (authorino_tpu/native/
// verdict_cache.py), None when dedup and the cache are off; verdict u8
// [>= count] and firing i32 [>= count] (None: no attribution) are written.
// Every input is checked first and a ValueError leaves the slot as it was.
// Then, without the interpreter lock: the decode and fan-out (vc::resolve),
// the slot's completion (none without a server), and the ticket's commit.
PyObject* fe_resolve_cut_py(PyObject*, PyObject* args) {
  PyObject *parts_o, *plan_o, *verdict_o, *firing_o;
  long long count, snap_id;
  int slot;
  if (!PyArg_ParseTuple(args, "OOLLiOO", &parts_o, &plan_o, &count, &snap_id, &slot,
                        &verdict_o, &firing_o))
    return nullptr;
  HeldBuffers bufs;
  const bool attribute = firing_o != Py_None;
  const Py_buffer* verdict = bufs.get(verdict_o, true, 1, "B", 1, 1, "verdict");
  if (verdict == nullptr) return nullptr;
  const Py_buffer* firing = nullptr;
  if (attribute && (firing = bufs.get(firing_o, true, 1, "il", 4, 4, "firing")) == nullptr)
    return nullptr;
  if (count < 0 || verdict->shape[0] < count || (firing != nullptr && firing->shape[0] < count)) {
    PyErr_SetString(PyExc_ValueError, "fe_resolve_cut: verdict/firing shorter than the cut");
    return nullptr;
  }

  vc::Fan fan{};
  VcTicket* ticket = nullptr;
  int64_t launched = count;
  if (plan_o != Py_None) {
    PyObject *ticket_o, *unique_o, *elig_o, *arr_o[5];
    // CutPlan's fields, in order
    if (!PyArg_ParseTuple(plan_o, "OOOOOOOO", &ticket_o, &arr_o[0], &arr_o[1], &arr_o[2],
                          &arr_o[3], &unique_o, &arr_o[4], &elig_o))
      return nullptr;
    static const char* names[5] = {"cached_rows", "cached_verdict", "cached_firing",
                                   "miss_rows", "inverse"};
    const Py_buffer* arr[5];
    for (int k = 0; k < 5; ++k)
      if ((arr[k] = bufs.get(arr_o[k], false, 1, "il", 4, 4, names[k])) == nullptr)
        return nullptr;
    Py_ssize_t n_unique = PyObject_Length(unique_o);
    if (n_unique < 0) return nullptr;
    launched = n_unique;
    fan = {(const int32_t*)arr[0]->buf, (const int32_t*)arr[1]->buf,
           (const int32_t*)arr[2]->buf, (const int32_t*)arr[3]->buf,
           (const int32_t*)arr[4]->buf, arr[0]->shape[0], arr[3]->shape[0]};
    if (arr[1]->shape[0] != fan.n_cached || arr[2]->shape[0] != fan.n_cached ||
        arr[4]->shape[0] != fan.n_miss || !indices_below(arr[0], fan.n_cached, count) ||
        !indices_below(arr[3], fan.n_miss, count) ||
        !indices_below(arr[4], fan.n_miss, launched)) {
      PyErr_SetString(PyExc_ValueError, "fe_resolve_cut: the plan's arrays disagree with "
                                        "each other or with the cut");
      return nullptr;
    }
    if (ticket_o != Py_None) {
      ticket = (VcTicket*)PyCapsule_GetPointer(ticket_o, VC_TICKET);
      if (ticket == nullptr ||
          PyCapsule_GetPointer(ticket->cache_cap, VC_CACHE) == nullptr)
        return nullptr;
      if (ticket->t.count > count) {
        PyErr_SetString(PyExc_ValueError, "fe_resolve_cut: the ticket's cut is longer");
        return nullptr;
      }
    }
  }

  PyObject* seq = PySequence_Fast(parts_o, "fe_resolve_cut: parts is not a sequence");
  if (seq == nullptr) return nullptr;
  std::vector<vc::Part> parts((size_t)PySequence_Fast_GET_SIZE(seq));
  for (size_t p = 0; p < parts.size(); ++p) {
    PyObject *packed_o, *at_o;
    long long n, E;
    const Py_buffer *packed, *at = nullptr;
    bool ok = PyArg_ParseTuple(PySequence_Fast_GET_ITEM(seq, (Py_ssize_t)p), "OOLL", &packed_o,
                               &at_o, &n, &E) &&
              (packed = bufs.get(packed_o, false, 2, "B", 1, 1, "a part's result", true)) !=
                  nullptr &&
              (at_o == Py_None ||
               (at = bufs.get(at_o, false, 1, "ilq", 4, 8, "a part's positions")) != nullptr);
    if (ok && (n < 0 || E < 0 || packed->shape[0] < n || packed->shape[1] < 1 ||
               (attribute && packed->shape[1] * 8 < 1 + 2 * E) ||
               (at != nullptr ? at->shape[0] < n || !indices_below(at, n, launched)
                              : n > launched))) {
      PyErr_SetString(PyExc_ValueError, "fe_resolve_cut: a part is shorter than its rows, "
                                        "narrower than its columns, or lands past the cut");
      ok = false;
    }
    if (!ok) {
      Py_DECREF(seq);
      return nullptr;
    }
    parts[p] = {(const uint8_t*)packed->buf, packed->strides[0], packed->strides[1], n, E,
                at != nullptr ? at->buf : nullptr, at != nullptr && at->itemsize == 8};
  }
  Py_DECREF(seq);

  fe::Server* S = fe::g_srv;
  uint8_t* v = (uint8_t*)verdict->buf;
  int32_t* f = firing != nullptr ? (int32_t*)firing->buf : nullptr;
  vc::Cache* cache =
      ticket != nullptr ? (vc::Cache*)PyCapsule_GetPointer(ticket->cache_cap, VC_CACHE) : nullptr;
  long long evicted = 0;
  Py_BEGIN_ALLOW_THREADS
  vc::resolve(parts.data(), parts.size(), launched, plan_o != Py_None ? &fan : nullptr, count,
              v, f);
  if (S != nullptr) fe::complete_batch(S, snap_id, slot, v);
  if (cache != nullptr) evicted = vc::commit(cache, ticket->t, v, f);
  Py_END_ALLOW_THREADS
  return PyLong_FromLongLong(evicted);
}

// vc_counts(cache) -> {hits, misses, adds, evictions, entries}
PyObject* vc_counts_py(PyObject*, PyObject* cache_o) {
  vc::Cache* c = (vc::Cache*)PyCapsule_GetPointer(cache_o, VC_CACHE);
  if (c == nullptr) return nullptr;
  return Py_BuildValue("{s:K,s:K,s:K,s:K,s:K}", "hits", c->hits.load(), "misses",
                       c->misses.load(), "adds", c->adds.load(), "evictions",
                       c->evictions.load(), "entries", c->entries.load());
}

PyMethodDef methods[] = {
    {"policy_new", policy_new_py, METH_VARARGS, "build native policy tables"},
    {"encode_docs", encode_docs, METH_VARARGS, "encode a batch of dict docs"},
    {"encode_json", encode_json_py, METH_VARARGS, "encode a JSON-blob batch"},
    {"fe_start", fe_start_py, METH_VARARGS, "start the native gRPC frontend"},
    {"fe_stop", fe_stop_py, METH_NOARGS, "stop the native gRPC frontend"},
    {"fe_port", fe_port_py, METH_NOARGS, "bound port of the frontend"},
    {"fe_swap", fe_swap_py, METH_VARARGS, "swap the frontend snapshot"},
    {"fe_wait_batch", fe_wait_batch_py, METH_VARARGS, "wait for a batch event"},
    {"fe_take_slow", fe_take_slow_py, METH_VARARGS, "take queued slow-lane requests"},
    {"fe_complete_batch", fe_complete_batch_py, METH_VARARGS, "complete a batch"},
    {"fe_complete_slow", fe_complete_slow_py, METH_VARARGS, "complete a slow request"},
    {"fe_complete_slow_many", fe_complete_slow_many_py, METH_VARARGS,
     "complete a batch of slow requests"},
    {"fe_add_variant", fe_add_variant_py, METH_VARARGS,
     "register a runtime credential plan variant"},
    {"fe_stats", fe_stats_py, METH_NOARGS, "frontend counters"},
    {"fe_drain_fc_counts", fe_drain_fc_counts_py, METH_NOARGS,
     "drain per-authconfig direct-decision counters"},
    {"fe_drain_durations", fe_drain_durations_py, METH_NOARGS,
     "drain per-authconfig duration histograms"},
    {"fe_stage_hist", fe_stage_hist_py, METH_NOARGS,
     "drain the on-box per-request stage histograms"},
    {"fe_loop_clock", fe_loop_clock_py, METH_NOARGS,
     "the epoll thread's loop clock, cumulative"},
    {"vc_new", vc_new_py, METH_VARARGS, "new native verdict cache"},
    {"vc_plan", vc_plan_py, METH_VARARGS,
     "probe the cache for a cut's rows and collapse its misses"},
    {"vc_commit", vc_commit_py, METH_VARARGS, "insert a planned cut's verdicts"},
    {"vc_counts", vc_counts_py, METH_O, "verdict cache counters"},
    {"fe_resolve_cut", fe_resolve_cut_py, METH_VARARGS,
     "decode, fan out, complete and commit a finished cut"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef module = {PyModuleDef_HEAD_INIT, "_atpuenc",
                      "native batch encoder", -1, methods};

}  // namespace

PyMODINIT_FUNC PyInit__atpuenc(void) {
  PyObject* json_mod = PyImport_ImportModule("json");
  if (json_mod == nullptr) return nullptr;
  g_json_dumps = PyObject_GetAttrString(json_mod, "dumps");
  Py_DECREF(json_mod);
  if (g_json_dumps == nullptr) return nullptr;
  g_dumps_kwargs = Py_BuildValue("{s:(s,s),s:O}", "separators", ",", ":",
                                 "ensure_ascii", Py_False);
  if (g_dumps_kwargs == nullptr) return nullptr;
  return PyModule_Create(&module);
}
