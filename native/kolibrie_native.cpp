// kolibrie_tpu native runtime: host-side hot paths in C++.
//
// Components (parity with the reference's native-Rust components; the Python
// package dispatches here when the shared library is available):
//
//  1. SDD engine  — hash-consed decision-diagram arena with apply/negate
//     caches, WMC with skipped-level weight correction, exactly-one
//     encoding, model enumeration, and the weight-substitution WMC gradient.
//     (reference: shared/src/sdd.rs, shared/src/diff_sdd.rs; Python twin:
//     kolibrie_tpu/reasoner/sdd.py — the two implementations must agree,
//     see tests/test_native.py)
//
//  2. N-Triples bulk tokenizer/interner — parses an N-Triples document into
//     a session-local unique-term table plus per-triple term indices in one
//     call, so the Python side interns only UNIQUE terms.
//     (reference: the parse hot path of kolibrie/src/sparql_database.rs;
//     Python twin: kolibrie_tpu/query/rdf_parsers.py)
//
// Exposed as a C ABI for ctypes (no pybind11 in this image).

#include <algorithm>
#include <cctype>
#include <cstdint>
#include <cstring>
#include <memory>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <vector>

// ───────────────────────────── SDD engine ────────────────────────────────

namespace {

constexpr int64_t FALSE_ID = 0;
constexpr int64_t TRUE_ID = 1;

struct Node {
  int64_t var, hi, lo;
};

struct NodeKey {
  int64_t var, hi, lo;
  bool operator==(const NodeKey &o) const {
    return var == o.var && hi == o.hi && lo == o.lo;
  }
};

struct NodeKeyHash {
  size_t operator()(const NodeKey &k) const {
    uint64_t h = 1469598103934665603ull;
    for (uint64_t x : {(uint64_t)k.var, (uint64_t)k.hi, (uint64_t)k.lo}) {
      h ^= x + 0x9e3779b97f4a7c15ull + (h << 6) + (h >> 2);
    }
    return (size_t)h;
  }
};

struct PairKey {
  int64_t a, b;
  int op;  // 0 = and, 1 = or
  bool operator==(const PairKey &o) const {
    return a == o.a && b == o.b && op == o.op;
  }
};

struct PairKeyHash {
  size_t operator()(const PairKey &k) const {
    uint64_t h = (uint64_t)k.a * 0x9e3779b97f4a7c15ull;
    h ^= (uint64_t)k.b + 0x9e3779b97f4a7c15ull + (h << 6) + (h >> 2);
    return (size_t)(h * 2 + k.op);
  }
};

struct VarInfo {
  double w_pos, w_neg;
  int kind;  // 0 = independent, 1 = exclusive
};

struct SddManager {
  std::vector<Node> nodes{{-1, 0, 0}, {-1, 1, 1}};
  std::unordered_map<NodeKey, int64_t, NodeKeyHash> unique;
  std::unordered_map<PairKey, int64_t, PairKeyHash> apply_cache;
  std::unordered_map<int64_t, int64_t> negate_cache;
  std::vector<VarInfo> vars;

  int64_t mk(int64_t var, int64_t hi, int64_t lo) {
    if (hi == lo) return hi;  // trimming rule
    NodeKey key{var, hi, lo};
    auto it = unique.find(key);
    if (it != unique.end()) return it->second;
    int64_t nid = (int64_t)nodes.size();
    nodes.push_back({var, hi, lo});
    unique.emplace(key, nid);
    return nid;
  }

  int64_t apply(int64_t a, int64_t b, int op) {
    if (op == 0) {
      if (a == FALSE_ID || b == FALSE_ID) return FALSE_ID;
      if (a == TRUE_ID) return b;
      if (b == TRUE_ID) return a;
    } else {
      if (a == TRUE_ID || b == TRUE_ID) return TRUE_ID;
      if (a == FALSE_ID) return b;
      if (b == FALSE_ID) return a;
    }
    if (a == b) return a;
    if (a > b) std::swap(a, b);
    PairKey key{a, b, op};
    auto it = apply_cache.find(key);
    if (it != apply_cache.end()) return it->second;
    int64_t va = nodes[a].var, vb = nodes[b].var;
    int64_t res;
    if (va == vb) {
      res = mk(va, apply(nodes[a].hi, nodes[b].hi, op),
               apply(nodes[a].lo, nodes[b].lo, op));
    } else if (va < vb) {
      res = mk(va, apply(nodes[a].hi, b, op), apply(nodes[a].lo, b, op));
    } else {
      res = mk(vb, apply(a, nodes[b].hi, op), apply(a, nodes[b].lo, op));
    }
    apply_cache.emplace(key, res);
    return res;
  }

  int64_t negate(int64_t a) {
    if (a == FALSE_ID) return TRUE_ID;
    if (a == TRUE_ID) return FALSE_ID;
    auto it = negate_cache.find(a);
    if (it != negate_cache.end()) return it->second;
    const Node n = nodes[a];
    int64_t res = mk(n.var, negate(n.hi), negate(n.lo));
    negate_cache[a] = res;
    negate_cache[res] = a;
    return res;
  }

  // WMC with skipped-level correction.  Level weights use a suffix scan
  // with zero-counting so a zero (w_pos + w_neg) cannot poison divisions.
  struct LevelWeights {
    std::vector<double> nzprod;  // product of nonzero sums in vars[0..i)
    std::vector<int> zeros;      // count of zero sums in vars[0..i)
    double range(int64_t a, int64_t b) const {  // product over vars[a..b)
      if (zeros[b] - zeros[a] > 0) return 0.0;
      return nzprod[b] / nzprod[a];
    }
  };

  LevelWeights level_weights() const {
    LevelWeights lw;
    size_t n = vars.size();
    lw.nzprod.resize(n + 1);
    lw.zeros.resize(n + 1);
    lw.nzprod[0] = 1.0;
    lw.zeros[0] = 0;
    for (size_t i = 0; i < n; i++) {
      double s = vars[i].w_pos + vars[i].w_neg;
      lw.zeros[i + 1] = lw.zeros[i] + (s == 0.0 ? 1 : 0);
      lw.nzprod[i + 1] = lw.nzprod[i] * (s == 0.0 ? 1.0 : s);
    }
    return lw;
  }

  double wmc_with(const LevelWeights &lw, int64_t root,
                  std::unordered_map<int64_t, double> &memo) const {
    int64_t n_vars = (int64_t)vars.size();
    // iterative post-order to avoid deep recursion on long chains
    struct Frame {
      int64_t node;
      int state;
    };
    std::vector<Frame> stack{{root, 0}};
    while (!stack.empty()) {
      Frame &f = stack.back();
      int64_t node = f.node;
      if (node == TRUE_ID || node == FALSE_ID || memo.count(node)) {
        stack.pop_back();
        continue;
      }
      const Node &n = nodes[node];
      if (f.state == 0) {
        f.state = 1;
        stack.push_back({n.hi, 0});
        stack.push_back({n.lo, 0});
        continue;
      }
      stack.pop_back();
      auto value_level = [&](int64_t child) -> std::pair<double, int64_t> {
        if (child == TRUE_ID) return {1.0, n_vars};
        if (child == FALSE_ID) return {0.0, n_vars};
        return {memo.at(child), nodes[child].var};
      };
      auto [whi, lhi] = value_level(n.hi);
      auto [wlo, llo] = value_level(n.lo);
      const VarInfo &vi = vars[n.var];
      memo[node] = vi.w_pos * whi * lw.range(n.var + 1, lhi) +
                   vi.w_neg * wlo * lw.range(n.var + 1, llo);
    }
    if (root == TRUE_ID) return lw.range(0, n_vars);
    if (root == FALSE_ID) return 0.0;
    return memo.at(root) * lw.range(0, nodes[root].var);
  }

  double wmc(int64_t root) const {
    LevelWeights lw = level_weights();
    std::unordered_map<int64_t, double> memo;
    return wmc_with(lw, root, memo);
  }
};

// ─────────────────────── N-Triples bulk tokenizer ────────────────────────

// Interning runs on a flat open-addressing table (power-of-two slots of
// {hash, id}, linear probing) over a bump arena that owns the term bytes.
// Compared with an unordered_map keyed by std::string this removes the
// per-term node allocation and the pointer-chasing probe — the 6M-probe/
// 1M-insert interning loop is the tokenizer's hot path.  Probing compares
// string_views straight into the raw input buffer; bytes are copied once,
// into the arena, on FIRST sight of a term.
struct NtArena {
  std::vector<std::unique_ptr<char[]>> blocks;
  size_t used = 0, cap = 0;

  const char *add(const char *src, size_t n) {
    // blocks.empty() guard: a zero-length first term (e.g. "<>") must not
    // dereference back() before any block exists
    if (blocks.empty() || used + n > cap) {
      cap = std::max<size_t>(n, (size_t)1 << 20);
      blocks.emplace_back(new char[cap]);
      used = 0;
    }
    char *dst = blocks.back().get() + used;
    std::memcpy(dst, src, n);
    used += n;
    return dst;
  }
};

struct NtSession {
  struct Slot {
    uint64_t hash;
    uint32_t id;  // 0 = empty (term ids are 1-based)
  };

  std::vector<uint32_t> ids;  // n_triples * 3, 1-based term indices
  std::vector<std::pair<const char *, uint32_t>> terms;  // (bytes, len)
  std::vector<Slot> slots = std::vector<Slot>(1 << 12);
  NtArena arena;
  int64_t term_bytes = 0;

  std::string_view term_view(uint32_t id) const {
    const auto &t = terms[id - 1];
    return std::string_view(t.first, t.second);
  }

  uint32_t intern_view(std::string_view sv) {
    uint64_t h = std::hash<std::string_view>{}(sv);
    size_t mask = slots.size() - 1;
    size_t i = (size_t)h & mask;
    while (true) {
      Slot &sl = slots[i];
      if (sl.id == 0) {
        uint32_t id = (uint32_t)terms.size() + 1;
        term_bytes += (int64_t)sv.size();
        terms.emplace_back(arena.add(sv.data(), sv.size()),
                           (uint32_t)sv.size());
        sl = {h, id};
        if (2 * ++count_ >= slots.size()) grow();
        return id;
      }
      if (sl.hash == h && term_view(sl.id) == sv) return sl.id;
      i = (i + 1) & mask;
    }
  }

 private:
  size_t count_ = 0;

  void grow() {
    std::vector<Slot> bigger(slots.size() * 2);
    size_t mask = bigger.size() - 1;
    for (const Slot &sl : slots) {
      if (sl.id == 0) continue;
      size_t i = (size_t)sl.hash & mask;
      while (bigger[i].id != 0) i = (i + 1) & mask;
      bigger[i] = sl;
    }
    slots.swap(bigger);
  }
};

// Append one unescaped char sequence (\t \n \r \" \' \\ \b \f \uXXXX
// \UXXXXXXXX — matching kolibrie_tpu/query/rdf_parsers._unescape).
bool append_unescaped(const char *s, int64_t len, std::string &out) {
  auto utf8_append = [&](uint32_t cp) {
    if (cp < 0x80) {
      out.push_back((char)cp);
    } else if (cp < 0x800) {
      out.push_back((char)(0xC0 | (cp >> 6)));
      out.push_back((char)(0x80 | (cp & 0x3F)));
    } else if (cp < 0x10000) {
      out.push_back((char)(0xE0 | (cp >> 12)));
      out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
      out.push_back((char)(0x80 | (cp & 0x3F)));
    } else {
      out.push_back((char)(0xF0 | (cp >> 18)));
      out.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
      out.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
      out.push_back((char)(0x80 | (cp & 0x3F)));
    }
  };
  auto hexval = [](char c) -> int {
    if (c >= '0' && c <= '9') return c - '0';
    if (c >= 'a' && c <= 'f') return c - 'a' + 10;
    if (c >= 'A' && c <= 'F') return c - 'A' + 10;
    return -1;
  };
  for (int64_t i = 0; i < len; i++) {
    char c = s[i];
    if (c != '\\' || i + 1 >= len) {
      out.push_back(c);
      continue;
    }
    char nxt = s[i + 1];
    switch (nxt) {
      case 't': out.push_back('\t'); i++; continue;
      case 'n': out.push_back('\n'); i++; continue;
      case 'r': out.push_back('\r'); i++; continue;
      case '"': out.push_back('"'); i++; continue;
      case '\'': out.push_back('\''); i++; continue;
      case '\\': out.push_back('\\'); i++; continue;
      case 'b': out.push_back('\b'); i++; continue;
      case 'f': out.push_back('\f'); i++; continue;
      case 'u':
      case 'U': {
        int ndig = nxt == 'u' ? 4 : 8;
        if (i + 2 + ndig <= len) {
          uint32_t cp = 0;
          bool ok = true;
          for (int d = 0; d < ndig; d++) {
            int hv = hexval(s[i + 2 + d]);
            if (hv < 0) { ok = false; break; }
            cp = cp * 16 + (uint32_t)hv;
          }
          if (ok) {
            utf8_append(cp);
            i += 1 + ndig;
            continue;
          }
        }
        out.push_back(c);
        continue;
      }
      default: out.push_back(c); continue;
    }
  }
  return true;
}

// Parser over raw bytes.  Returns 0 on success, -1 on syntax error, -2 on a
// construct the fast path does not support (caller falls back to Python).
//
// Terms whose stored form is an exact substring of the input (IRIs without
// the angle brackets, blank nodes, plain/lang literals without escapes)
// intern as string_views into ``data`` — no copy, no allocation unless the
// term is new.  Only escaped and datatype-suffixed literals materialize
// into the reused scratch buffer.
int nt_parse_impl(const char *data, int64_t len, NtSession &out) {
  int64_t i = 0;
  int term_in_line = 0;
  uint32_t line_ids[3];
  std::string scratch;
  while (i < len) {
    char c = data[i];
    if (c == ' ' || c == '\t' || c == '\r' || c == '\n') { i++; continue; }
    if (c == '#') {  // comment to end of line
      while (i < len && data[i] != '\n') i++;
      continue;
    }
    if (c == '.') {
      if (term_in_line != 3) return -1;
      out.ids.insert(out.ids.end(), line_ids, line_ids + 3);
      term_in_line = 0;
      i++;
      continue;
    }
    if (term_in_line == 3) return -1;  // missing '.'
    std::string_view view;
    if (c == '<') {
      if (i + 1 < len && data[i + 1] == '<') return -2;  // RDF-star: fallback
      int64_t j = i + 1;
      while (j < len && data[j] != '>') {
        if (data[j] == '\n') return -1;
        j++;
      }
      if (j >= len) return -1;
      view = std::string_view(data + i + 1, (size_t)(j - i - 1));
      i = j + 1;
    } else if (c == '_') {
      if (i + 1 >= len || data[i + 1] != ':') return -1;
      int64_t j = i + 2;
      while (j < len && (isalnum((unsigned char)data[j]) || data[j] == '_' ||
                         data[j] == '-' || data[j] == '.')) {
        j++;
      }
      // a trailing '.' belongs to the statement, not the label
      while (j > i + 2 && data[j - 1] == '.') j--;
      view = std::string_view(data + i, (size_t)(j - i));
      i = j;
    } else if (c == '"') {
      int64_t j = i + 1;
      bool escaped = false;
      while (j < len) {
        if (data[j] == '\\') { escaped = true; j += 2; continue; }
        if (data[j] == '"') break;
        j++;
      }
      if (j >= len) return -1;
      int64_t body_start = i, body_end = j + 1;  // inclusive of both quotes
      i = j + 1;
      if (i + 1 < len && data[i] == '^' && data[i + 1] == '^') {
        i += 2;
        if (i >= len || data[i] != '<') return -2;  // prefixed datatype
        int64_t k = i + 1;
        while (k < len && data[k] != '>') k++;
        if (k >= len) return -1;
        // stored form strips the datatype's angle brackets — always
        // materialized ("..."^^iri differs from the input "..."^^<iri>)
        scratch.clear();
        scratch.push_back('"');
        if (!append_unescaped(data + body_start + 1,
                              body_end - body_start - 2, scratch)) {
          return -1;
        }
        scratch.push_back('"');
        scratch.append("^^");
        scratch.append(data + i + 1, (size_t)(k - i - 1));
        i = k + 1;
        view = std::string_view(scratch);
      } else {
        int64_t end = body_end;
        if (i < len && data[i] == '@') {
          int64_t k = i + 1;
          while (k < len &&
                 (isalnum((unsigned char)data[k]) || data[k] == '-')) {
            k++;
          }
          end = k;
          i = k;
        }
        if (!escaped) {
          // quotes and language tag are verbatim input bytes
          view = std::string_view(data + body_start, (size_t)(end - body_start));
        } else {
          scratch.clear();
          scratch.push_back('"');
          if (!append_unescaped(data + body_start + 1,
                                body_end - body_start - 2, scratch)) {
            return -1;
          }
          scratch.push_back('"');
          scratch.append(data + body_end, (size_t)(end - body_end));
          view = std::string_view(scratch);
        }
      }
    } else {
      return -2;  // prefixed name / directive / number: Turtle, not N-Triples
    }
    line_ids[term_in_line++] = out.intern_view(view);
  }
  if (term_in_line != 0) return -1;  // unterminated statement
  return 0;
}

// Multithreaded parse: split the document at newline boundaries, parse each
// chunk into a thread-local session, then merge the term tables (remapping
// each chunk's ids).  N-Triples statements MAY legally span lines; a chunk
// cut inside a statement makes that chunk's parse fail (-1 unterminated /
// malformed), in which case the caller falls back to the single-threaded
// whole-document parse — one-statement-per-line data (the universal layout)
// always takes the parallel path.  Mirrors the reference's chunked parallel
// parse + dictionary merge design (sparql_database.rs:407-434,
// dictionary.rs:82-90) with threads in place of a rayon pool.
int nt_parse_mt_impl(const char *data, int64_t len, int nthreads,
                     NtSession &out) {
  if (nthreads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nthreads = hc ? (int)hc : 1;
    // auto mode: threading only pays off past ~1MB of input
    const int64_t kMinChunk = 1 << 20;
    if ((int64_t)nthreads > len / kMinChunk) {
      nthreads = (int)(len / kMinChunk);
      if (nthreads < 1) nthreads = 1;
    }
  }
  // an explicit nthreads >= 2 is honored regardless of input size so the
  // chunk-split/merge path is exercisable by tests on small documents
  if (nthreads > 16) nthreads = 16;
  if (len > 0 && (int64_t)nthreads > len) nthreads = (int)len;
  if (nthreads <= 1) return nt_parse_impl(data, len, out);

  std::vector<int64_t> starts(nthreads + 1);
  starts[0] = 0;
  starts[nthreads] = len;
  for (int t = 1; t < nthreads; t++) {
    int64_t pos = len * t / nthreads;
    if (pos < starts[t - 1]) pos = starts[t - 1];
    while (pos < len && data[pos] != '\n') pos++;
    starts[t] = pos < len ? pos + 1 : len;
  }
  std::vector<NtSession> locals(nthreads);
  std::vector<int> rcs(nthreads, 0);
  std::vector<std::thread> workers;
  workers.reserve(nthreads);
  // exceptions must not cross a thread boundary (std::terminate would
  // abort the embedding Python process): catch inside the worker, and
  // treat a failed spawn (RLIMIT_NPROC etc.) as a single-thread fallback
  for (int t = 0; t < nthreads; t++) {
    try {
      workers.emplace_back([&, t] {
        try {
          rcs[t] = nt_parse_impl(data + starts[t], starts[t + 1] - starts[t],
                                 locals[t]);
        } catch (...) {
          rcs[t] = -3;
        }
      });
    } catch (const std::system_error &) {
      for (int u = t; u < nthreads; u++) rcs[u] = -3;
      break;
    }
  }
  for (auto &w : workers) w.join();
  for (int t = 0; t < nthreads; t++) {
    if (rcs[t] == -2) return -2;  // unsupported construct: Python decides
    if (rcs[t] != 0) return nt_parse_impl(data, len, out);  // spanning stmt
  }
  // merge: chunk 0 seeds the output; later chunks remap through interning
  // (locals stay alive through the loop, so views into their arenas are
  // valid while out.intern_view copies the bytes it keeps)
  out = std::move(locals[0]);
  for (int t = 1; t < nthreads; t++) {
    NtSession &loc = locals[t];
    std::vector<uint32_t> remap(loc.terms.size() + 1);
    for (size_t k = 0; k < loc.terms.size(); k++) {
      remap[k + 1] = out.intern_view(
          std::string_view(loc.terms[k].first, loc.terms[k].second));
    }
    size_t base = out.ids.size();
    out.ids.resize(base + loc.ids.size());
    for (size_t k = 0; k < loc.ids.size(); k++) {
      out.ids[base + k] = remap[loc.ids[k]];
    }
  }
  return 0;
}

// ───────────────────────── Turtle fast path ─────────────────────────────
//
// Native tokenizer for the common bulk-load subset of Turtle: @prefix /
// PREFIX directives, IRIs, prefixed names, 'a', literals (escapes, @lang,
// ^^<iri> and ^^pname datatypes), numeric/boolean shorthand, blank-node
// labels, and ';' / ',' predicate/object lists.  Stored term forms match
// kolibrie_tpu/query/rdf_parsers.py exactly (IRIs expanded and
// unbracketed; literals keep quotes + suffix with the datatype IRI
// expanded; numbers/booleans become "<text>"^^xsd:<type>).
//
// Returns -2 (Python fallback) for everything else: RDF-star '<<',
// anonymous/blank property lists '[', collections '(', single-quoted and
// multiline strings, @base/BASE.  Mirrors the reference's streamed chunked
// Turtle ingestion (sparql_database.rs:729 + the crossbeam pipeline at
// :401-571) as a thread-chunked parse with dictionary merge.

struct TtlPrefixEnv {
  std::unordered_map<std::string, std::string> map;
  bool frozen = false;  // MT chunk mode: directives may not ADD or CHANGE
};

inline bool ttl_is_ws(char c) {
  return c == ' ' || c == '\t' || c == '\r' || c == '\n';
}

inline bool ttl_pname_prefix_char(char c) {
  return isalnum((unsigned char)c) || c == '_' || c == '.' || c == '-';
}

inline bool ttl_pname_local_char(char c) {
  return isalnum((unsigned char)c) || c == '_' || c == '.' || c == '%' ||
         c == '-';
}

// Skip whitespace and comments; returns index of next significant byte.
inline int64_t ttl_skip(const char *data, int64_t len, int64_t i) {
  while (i < len) {
    char c = data[i];
    if (ttl_is_ws(c)) { i++; continue; }
    if (c == '#') {
      while (i < len && data[i] != '\n') i++;
      continue;
    }
    break;
  }
  return i;
}

// Parse one term starting at data[i]; interns the stored form into `out`
// and advances i.  `pos` 0/1/2 = subject/predicate/object.  Returns 0 ok,
// -1 syntax error, -2 unsupported construct.
int ttl_term(const char *data, int64_t len, int64_t &i, int pos,
             const TtlPrefixEnv &env, NtSession &out, std::string &scratch,
             uint32_t &id_out) {
  char c = data[i];
  if (c == '<') {
    if (i + 1 < len && data[i + 1] == '<') return -2;  // Turtle-star
    int64_t j = i + 1;
    while (j < len && data[j] != '>') {
      if (data[j] == '\n') return -1;
      j++;
    }
    if (j >= len) return -1;
    id_out = out.intern_view(std::string_view(data + i + 1, (size_t)(j - i - 1)));
    i = j + 1;
    return 0;
  }
  if (c == '_') {
    if (i + 1 >= len || data[i + 1] != ':') return -1;
    int64_t j = i + 2;
    // label charset matches the Python tokenizer's blank regex [\w-]+
    // exactly (NO dots) so both paths store identical labels
    while (j < len && (isalnum((unsigned char)data[j]) || data[j] == '_' ||
                       data[j] == '-')) {
      j++;
    }
    id_out = out.intern_view(std::string_view(data + i, (size_t)(j - i)));
    i = j;
    return 0;
  }
  if (c == '"') {
    if (i + 2 < len && data[i + 1] == '"' && data[i + 2] == '"') {
      return -2;  // multiline string: Python handles
    }
    int64_t j = i + 1;
    bool escaped = false;
    while (j < len) {
      if (data[j] == '\\') { escaped = true; j += 2; continue; }
      if (data[j] == '"') break;
      if (data[j] == '\n') return -1;  // raw newline illegal in '"' string
      j++;
    }
    if (j >= len) return -1;
    int64_t body_start = i, body_end = j + 1;
    i = j + 1;
    if (i + 1 < len && data[i] == '^' && data[i + 1] == '^') {
      i += 2;
      scratch.clear();
      scratch.push_back('"');
      if (!append_unescaped(data + body_start + 1, body_end - body_start - 2,
                            scratch)) {
        return -1;
      }
      scratch.push_back('"');
      scratch.append("^^");
      if (i < len && data[i] == '<') {
        int64_t k = i + 1;
        while (k < len && data[k] != '>') k++;
        if (k >= len) return -1;
        scratch.append(data + i + 1, (size_t)(k - i - 1));
        i = k + 1;
      } else {
        // prefixed datatype
        int64_t k = i;
        while (k < len && data[k] != ':' && ttl_pname_prefix_char(data[k])) k++;
        if (k >= len || data[k] != ':') return -1;
        std::string pfx(data + i, (size_t)(k - i));
        auto it = env.map.find(pfx);
        if (it == env.map.end()) return -1;
        int64_t m = k + 1;
        while (m < len && ttl_pname_local_char(data[m])) m++;
        if (m > k + 1 && data[m - 1] == '.') return -2;  // see ttl_term pname
        scratch.append(it->second);
        scratch.append(data + k + 1, (size_t)(m - k - 1));
        i = m;
      }
      id_out = out.intern_view(std::string_view(scratch));
      return 0;
    }
    int64_t end = body_end;
    if (i < len && data[i] == '@') {
      int64_t k = i + 1;
      while (k < len && (isalnum((unsigned char)data[k]) || data[k] == '-')) k++;
      end = k;
      i = k;
    }
    if (!escaped) {
      id_out = out.intern_view(
          std::string_view(data + body_start, (size_t)(end - body_start)));
    } else {
      scratch.clear();
      scratch.push_back('"');
      if (!append_unescaped(data + body_start + 1, body_end - body_start - 2,
                            scratch)) {
        return -1;
      }
      scratch.push_back('"');
      scratch.append(data + body_end, (size_t)(end - body_end));
      id_out = out.intern_view(std::string_view(scratch));
    }
    return 0;
  }
  if (c == '\'') return -2;  // single-quoted string: Python handles
  if (c == '[' || c == '(') return -2;  // bnode property list / collection
  if (c == '+' || c == '-' || isdigit((unsigned char)c)) {
    int64_t j = i;
    if (data[j] == '+' || data[j] == '-') j++;
    int64_t digits_start = j;
    while (j < len && isdigit((unsigned char)data[j])) j++;
    if (j == digits_start) return -1;
    bool is_decimal = false, is_double = false;
    if (j + 1 < len && data[j] == '.' && isdigit((unsigned char)data[j + 1])) {
      is_decimal = true;
      j++;
      while (j < len && isdigit((unsigned char)data[j])) j++;
    }
    if (j < len && (data[j] == 'e' || data[j] == 'E')) {
      int64_t k = j + 1;
      if (k < len && (data[k] == '+' || data[k] == '-')) k++;
      if (k < len && isdigit((unsigned char)data[k])) {
        is_double = true;
        j = k;
        while (j < len && isdigit((unsigned char)data[j])) j++;
      }
    }
    scratch.clear();
    scratch.push_back('"');
    scratch.append(data + i, (size_t)(j - i));
    scratch.append("\"^^http://www.w3.org/2001/XMLSchema#");
    scratch.append(is_double ? "double" : is_decimal ? "decimal" : "integer");
    id_out = out.intern_view(std::string_view(scratch));
    i = j;
    return 0;
  }
  if (isalpha((unsigned char)c) || c == ':') {
    // pname, 'a', true/false — scan prefix part up to ':'
    int64_t j = i;
    while (j < len && data[j] != ':' && ttl_pname_prefix_char(data[j])) j++;
    if (j < len && data[j] == ':') {
      std::string pfx(data + i, (size_t)(j - i));
      auto it = env.map.find(pfx);
      if (it == env.map.end()) return -1;  // undefined / not-yet-seen prefix
      int64_t m = j + 1;
      while (m < len && ttl_pname_local_char(data[m])) m++;
      if (m > j + 1 && data[m - 1] == '.') {
        // 'ex:foo.' — dot-terminated pname.  Turtle grammar says the dot
        // is the statement terminator, but the Python tokenizer keeps it
        // in the local name; native MUST NOT silently store different
        // triples than the fallback, so let Python decide.
        return -2;
      }
      scratch.clear();
      scratch.append(it->second);
      scratch.append(data + j + 1, (size_t)(m - j - 1));
      id_out = out.intern_view(std::string_view(scratch));
      i = m;
      return 0;
    }
    std::string_view word(data + i, (size_t)(j - i));
    if (pos == 1 && word == "a") {
      id_out = out.intern_view(
          "http://www.w3.org/1999/02/22-rdf-syntax-ns#type");
      i = j;
      return 0;
    }
    if (pos == 2 && (word == "true" || word == "false")) {
      scratch.clear();
      scratch.push_back('"');
      scratch.append(word);
      scratch.append("\"^^http://www.w3.org/2001/XMLSchema#boolean");
      id_out = out.intern_view(std::string_view(scratch));
      i = j;
      return 0;
    }
    return -2;  // bare keyword (BASE, GRAPH, ...) — Python decides
  }
  return -1;
}

// Parse an @prefix / PREFIX directive starting at data[i] (i is at the
// keyword).  Applies it to env (or verifies consistency when frozen).
// Returns 0 ok, -1 error or frozen-mode mismatch, 1 = not a directive.
int ttl_directive(const char *data, int64_t len, int64_t &i,
                  TtlPrefixEnv &env) {
  auto starts = [&](const char *kw, int64_t n) {
    if (i + n >= len) return false;
    for (int64_t k = 0; k < n; k++) {
      char a = data[i + k], b = kw[k];
      if (a != b && a != (char)toupper((unsigned char)b)) return false;
    }
    // keyword must be followed by whitespace — 'prefix:x' is a pname
    return ttl_is_ws(data[i + n]);
  };
  auto at_kw = [&](const char *kw, int64_t n) {
    if (i + n >= len) return false;
    if (std::memcmp(data + i, kw, (size_t)n) != 0) return false;
    return ttl_is_ws(data[i + n]);
  };
  bool at_prefix = false, sparql_style = false;
  if (data[i] == '@') {
    if (at_kw("@prefix", 7)) {
      at_prefix = true;
      i += 7;
    } else {
      return (i + 1 < len && data[i + 1] == 'b') ? -2 : -1;  // @base
    }
  } else if (starts("prefix", 6)) {
    sparql_style = true;
    i += 6;
  } else if (starts("base", 4)) {
    return -2;
  } else {
    return 1;
  }
  i = ttl_skip(data, len, i);
  int64_t j = i;
  while (j < len && data[j] != ':' && ttl_pname_prefix_char(data[j])) j++;
  if (j >= len || data[j] != ':') return -1;
  std::string pfx(data + i, (size_t)(j - i));
  i = ttl_skip(data, len, j + 1);
  if (i >= len || data[i] != '<') return -1;
  int64_t k = i + 1;
  while (k < len && data[k] != '>') k++;
  if (k >= len) return -1;
  std::string iri(data + i + 1, (size_t)(k - i - 1));
  i = k + 1;
  if (at_prefix) {  // '@prefix' requires the terminating '.'
    i = ttl_skip(data, len, i);
    if (i >= len || data[i] != '.') return -1;
    i++;
  } else if (!sparql_style) {
    return -1;
  }
  auto it = env.map.find(pfx);
  if (env.frozen) {
    // MT chunk: the sequential pre-pass already registered every
    // line-leading directive; anything new or conflicting forces the
    // single-threaded re-parse
    if (it == env.map.end() || it->second != iri) return -1;
  } else {
    env.map[pfx] = std::move(iri);
  }
  return 0;
}

int ttl_parse_impl(const char *data, int64_t len, TtlPrefixEnv &env,
                   NtSession &out) {
  int64_t i = 0;
  std::string scratch;
  while (true) {
    i = ttl_skip(data, len, i);
    if (i >= len) return 0;
    int drc = ttl_directive(data, len, i, env);
    if (drc == 0) continue;
    if (drc < 0) return drc;
    uint32_t s_id, p_id, o_id;
    int rc = ttl_term(data, len, i, 0, env, out, scratch, s_id);
    if (rc != 0) return rc;
    while (true) {  // predicate list
      i = ttl_skip(data, len, i);
      if (i >= len) return -1;
      rc = ttl_term(data, len, i, 1, env, out, scratch, p_id);
      if (rc != 0) return rc;
      while (true) {  // object list
        i = ttl_skip(data, len, i);
        if (i >= len) return -1;
        rc = ttl_term(data, len, i, 2, env, out, scratch, o_id);
        if (rc != 0) return rc;
        out.ids.push_back(s_id);
        out.ids.push_back(p_id);
        out.ids.push_back(o_id);
        i = ttl_skip(data, len, i);
        if (i < len && data[i] == ',') { i++; continue; }
        break;
      }
      if (i < len && data[i] == ';') {
        i++;
        i = ttl_skip(data, len, i);
        if (i < len && (data[i] == '.' || data[i] == ';')) {
          // trailing ';' before '.' (legal); empty ';;' also tolerated
          while (i < len && data[i] == ';') i = ttl_skip(data, len, i + 1);
        }
        if (i < len && data[i] == '.') break;
        continue;
      }
      break;
    }
    if (i >= len || data[i] != '.') return -1;
    i++;
  }
}

// Sequential pre-pass over line-leading directives (MT mode): applies them
// in document order.  Returns false (→ exact sequential parse) if a
// prefix is REDEFINED to a different IRI, or if any directive appears
// AFTER the first statement — pre-applying such a directive to every
// chunk would let a statement use a prefix declared later in the
// document, which the sequential (and Python) parse correctly rejects.
bool ttl_collect_directives(const char *data, int64_t len, TtlPrefixEnv &env) {
  int64_t i = 0;
  bool statements_started = false;
  while (i < len) {
    int64_t ls = i;
    while (ls < len && (data[ls] == ' ' || data[ls] == '\t')) ls++;
    bool blank_or_comment =
        ls >= len || data[ls] == '\n' || data[ls] == '\r' || data[ls] == '#';
    if (!blank_or_comment &&
        (data[ls] == '@' || data[ls] == 'P' || data[ls] == 'p')) {
      int64_t j = ls;
      TtlPrefixEnv probe;  // reuse parser; apply manually to detect conflicts
      int rc = ttl_directive(data, len, j, probe);
      if (rc == 0 && !probe.map.empty()) {
        if (statements_started) return false;  // forward-reference hazard
        auto &kv = *probe.map.begin();
        auto it = env.map.find(kv.first);
        if (it != env.map.end() && it->second != kv.second) return false;
        env.map[kv.first] = kv.second;
      } else if (rc == 1) {
        statements_started = true;  // a pname like 'prefix:x' = a statement
      }
    } else if (!blank_or_comment) {
      statements_started = true;
    }
    while (i < len && data[i] != '\n') i++;
    i++;
  }
  return true;
}

// Chunked multithreaded Turtle parse.  Chunks split after '.' + newline
// (the statement terminator; '.' inside IRIs/literals never precedes a raw
// newline, and multiline strings return -2 from whichever chunk holds the
// opener before any merge).  Any chunk failure falls back to the exact
// sequential parse.
int ttl_parse_mt_impl(const char *data, int64_t len, int nthreads,
                      TtlPrefixEnv &env, NtSession &out) {
  if (nthreads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nthreads = hc ? (int)hc : 1;
    const int64_t kMinChunk = 1 << 20;
    if ((int64_t)nthreads > len / kMinChunk) {
      nthreads = (int)(len / kMinChunk);
      if (nthreads < 1) nthreads = 1;
    }
  }
  if (nthreads > 16) nthreads = 16;
  if (len > 0 && (int64_t)nthreads > len) nthreads = (int)len;
  if (nthreads <= 1) return ttl_parse_impl(data, len, env, out);

  TtlPrefixEnv shared = env;
  if (!ttl_collect_directives(data, len, shared)) {
    return ttl_parse_impl(data, len, env, out);  // redefinition: sequential
  }
  shared.frozen = true;

  std::vector<int64_t> starts(nthreads + 1);
  starts[0] = 0;
  starts[nthreads] = len;
  for (int t = 1; t < nthreads; t++) {
    int64_t pos = len * t / nthreads;
    if (pos < starts[t - 1]) pos = starts[t - 1];
    // advance to the first newline whose preceding significant byte is '.'
    while (pos < len) {
      if (data[pos] == '\n') {
        int64_t b = pos - 1;
        while (b >= starts[t - 1] && (data[b] == ' ' || data[b] == '\t' ||
                                      data[b] == '\r')) {
          b--;
        }
        if (b >= starts[t - 1] && data[b] == '.') break;
      }
      pos++;
    }
    starts[t] = pos < len ? pos + 1 : len;
  }
  std::vector<NtSession> locals(nthreads);
  std::vector<int> rcs(nthreads, 0);
  std::vector<std::thread> workers;
  workers.reserve(nthreads);
  for (int t = 0; t < nthreads; t++) {
    try {
      workers.emplace_back([&, t] {
        try {
          TtlPrefixEnv chunk_env = shared;  // const-used; cheap map copy
          rcs[t] = ttl_parse_impl(data + starts[t], starts[t + 1] - starts[t],
                                  chunk_env, locals[t]);
        } catch (...) {
          rcs[t] = -3;
        }
      });
    } catch (const std::system_error &) {
      for (int u = t; u < nthreads; u++) rcs[u] = -3;
      break;
    }
  }
  for (auto &w : workers) w.join();
  for (int t = 0; t < nthreads; t++) {
    if (rcs[t] == -2) return -2;
    if (rcs[t] != 0) return ttl_parse_impl(data, len, env, out);
  }
  out = std::move(locals[0]);
  for (int t = 1; t < nthreads; t++) {
    NtSession &loc = locals[t];
    std::vector<uint32_t> remap(loc.terms.size() + 1);
    for (size_t k = 0; k < loc.terms.size(); k++) {
      remap[k + 1] = out.intern_view(
          std::string_view(loc.terms[k].first, loc.terms[k].second));
    }
    size_t base = out.ids.size();
    out.ids.resize(base + loc.ids.size());
    for (size_t k = 0; k < loc.ids.size(); k++) {
      out.ids[base + k] = remap[loc.ids[k]];
    }
  }
  env = std::move(shared);
  env.frozen = false;
  return 0;
}

struct TtlSession {
  NtSession nt;  // FIRST member: kn_nt_* accessors work on the same layout
  std::string prefix_blob;  // final prefixes: pfx \x1F iri \x1E ...
};

// ───────────────────────── RDF/XML fast path ────────────────────────────
//
// Streaming byte-level parser for the common bulk shape of RDF/XML — the
// reference's primary load format (its quick-xml streamed ingestion,
// sparql_database.rs:401-571): a root <rdf:RDF> with xmlns declarations,
// node elements <rdf:Description rdf:about="..."> (or typed node elements
// → rdf:type), non-rdf attributes as literal properties, and property
// elements carrying rdf:resource / rdf:nodeID / rdf:datatype / xml:lang /
// text content.  Stored term forms match rdf_parsers.parse_rdf_xml
// exactly.  Returns -2 (Python ElementTree fallback) for: default xmlns,
// nested node elements, fresh blank nodes (no about/ID/nodeID),
// parseType, CDATA, DOCTYPE, processing instructions beyond the XML decl,
// or any rdf:-namespace construct outside the supported set.

static const char *kRdfNs = "http://www.w3.org/1999/02/22-rdf-syntax-ns#";
static const char *kXmlNs = "http://www.w3.org/XML/1998/namespace";

struct RxParser {
  const char *d;
  int64_t n;
  int64_t i = 0;
  NtSession *out;
  std::unordered_map<std::string, std::string> ns;  // prefix -> iri
  std::string scratch, scratch2;

  bool ws(char c) {
    return c == ' ' || c == '\t' || c == '\r' || c == '\n';
  }
  void skip_ws() {
    while (i < n && ws(d[i])) i++;
  }
  // Skip <?...?> and <!-- ... -->; returns -2 on DOCTYPE/CDATA, 0 else.
  int skip_misc() {
    while (true) {
      skip_ws();
      if (i + 1 >= n || d[i] != '<') return 0;
      if (d[i + 1] == '?') {
        i += 2;
        while (i + 1 < n && !(d[i] == '?' && d[i + 1] == '>')) i++;
        if (i + 1 >= n) return -1;
        i += 2;
        continue;
      }
      if (i + 3 < n && d[i + 1] == '!' && d[i + 2] == '-' && d[i + 3] == '-') {
        i += 4;
        while (i + 2 < n &&
               !(d[i] == '-' && d[i + 1] == '-' && d[i + 2] == '>')) {
          i++;
        }
        if (i + 2 >= n) return -1;
        i += 3;
        continue;
      }
      if (d[i + 1] == '!') return -2;  // DOCTYPE / CDATA
      return 0;
    }
  }
  // XML entity unescape of [s, s+len) into dst (appends).  ``attr`` turns
  // on XML attribute-value normalization (literal tab/newline/CR → space);
  // text content gets line-ending normalization (\r\n and \r → \n) — both
  // are what ElementTree produces, and the native path must store
  // byte-identical terms to the Python fallback.
  bool unescape(const char *s, int64_t len, std::string &dst,
                bool attr = false) {
    for (int64_t k = 0; k < len; k++) {
      char c = s[k];
      if (c != '&') {
        if (attr && (c == '\t' || c == '\n' || c == '\r')) {
          // XML line-ending normalization runs BEFORE attribute-value
          // normalization, so a literal \r\n is ONE space (ElementTree
          // parity), not two
          dst.push_back(' ');
          if (c == '\r' && k + 1 < len && s[k + 1] == '\n') k++;
        } else if (!attr && c == '\r') {
          dst.push_back('\n');
          if (k + 1 < len && s[k + 1] == '\n') k++;  // \r\n → \n
        } else {
          dst.push_back(c);
        }
        continue;
      }
      int64_t semi = k + 1;
      while (semi < len && s[semi] != ';' && semi - k < 12) semi++;
      if (semi >= len || s[semi] != ';') return false;
      std::string_view ent(s + k + 1, (size_t)(semi - k - 1));
      if (ent == "amp") dst.push_back('&');
      else if (ent == "lt") dst.push_back('<');
      else if (ent == "gt") dst.push_back('>');
      else if (ent == "quot") dst.push_back('"');
      else if (ent == "apos") dst.push_back('\'');
      else if (!ent.empty() && ent[0] == '#') {
        uint32_t cp = 0;
        bool hex = ent.size() > 1 && (ent[1] == 'x' || ent[1] == 'X');
        for (size_t t = hex ? 2 : 1; t < ent.size(); t++) {
          char h = ent[t];
          int v = h >= '0' && h <= '9' ? h - '0'
                  : h >= 'a' && h <= 'f' ? h - 'a' + 10
                  : h >= 'A' && h <= 'F' ? h - 'A' + 10
                  : -1;
          if (v < 0 || (!hex && v > 9)) return false;
          cp = cp * (hex ? 16 : 10) + (uint32_t)v;
        }
        // UTF-8 append (shares logic shape with append_unescaped)
        if (cp < 0x80) dst.push_back((char)cp);
        else if (cp < 0x800) {
          dst.push_back((char)(0xC0 | (cp >> 6)));
          dst.push_back((char)(0x80 | (cp & 0x3F)));
        } else if (cp < 0x10000) {
          dst.push_back((char)(0xE0 | (cp >> 12)));
          dst.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
          dst.push_back((char)(0x80 | (cp & 0x3F)));
        } else {
          dst.push_back((char)(0xF0 | (cp >> 18)));
          dst.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
          dst.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
          dst.push_back((char)(0x80 | (cp & 0x3F)));
        }
      } else {
        return false;
      }
      k = semi;
    }
    return true;
  }

  struct Attr {
    std::string_view name;  // raw qname, e.g. "rdf:about"
    std::string value;      // unescaped
  };

  // Parse a start tag at d[i]=='<'; fills qname + attrs, sets self_close.
  int tag(std::string_view &qname, std::vector<Attr> &attrs,
          bool &self_close, bool &is_close) {
    attrs.clear();
    if (d[i] != '<') return -1;
    i++;
    is_close = i < n && d[i] == '/';
    if (is_close) i++;
    int64_t s0 = i;
    while (i < n && !ws(d[i]) && d[i] != '>' && d[i] != '/') i++;
    qname = std::string_view(d + s0, (size_t)(i - s0));
    if (qname.empty()) return -1;
    self_close = false;
    while (true) {
      skip_ws();
      if (i >= n) return -1;
      if (d[i] == '>') {
        i++;
        return 0;
      }
      if (d[i] == '/' && i + 1 < n && d[i + 1] == '>') {
        self_close = true;
        i += 2;
        return 0;
      }
      int64_t a0 = i;
      while (i < n && d[i] != '=' && !ws(d[i])) i++;
      std::string_view aname(d + a0, (size_t)(i - a0));
      skip_ws();
      if (i >= n || d[i] != '=') return -1;
      i++;
      skip_ws();
      if (i >= n || (d[i] != '"' && d[i] != '\'')) return -1;
      char q = d[i++];
      int64_t v0 = i;
      while (i < n && d[i] != q) i++;
      if (i >= n) return -1;
      Attr a;
      a.name = aname;
      if (!unescape(d + v0, i - v0, a.value, /*attr=*/true)) return -1;
      i++;  // closing quote
      attrs.push_back(std::move(a));
    }
  }

  // Resolve "pfx:local" via the ns map into scratch2; nullptr prefix → -2.
  int expand(std::string_view qname, std::string &dst) {
    size_t colon = qname.find(':');
    if (colon == std::string_view::npos) return -2;  // default-ns element
    auto it = ns.find(std::string(qname.substr(0, colon)));
    if (it == ns.end()) return -2;
    dst.clear();
    dst.append(it->second);
    dst.append(qname.substr(colon + 1));
    return 0;
  }

  bool is_rdf(std::string_view qname, const char *local) {
    size_t colon = qname.find(':');
    if (colon == std::string_view::npos) return false;
    auto it = ns.find(std::string(qname.substr(0, colon)));
    return it != ns.end() && it->second == kRdfNs &&
           qname.substr(colon + 1) == std::string_view(local);
  }

  // Parse the XML decl/comments + root <rdf:RDF ...> open tag; fills the
  // ns map and leaves ``i`` at the first body byte.  ``root_closed`` set
  // when the root self-closes (empty document).
  int parse_root(bool &root_closed) {
    int rc = skip_misc();
    if (rc != 0) return rc;
    std::string_view qname;
    std::vector<Attr> attrs;
    bool self_close, is_close;
    rc = tag(qname, attrs, self_close, is_close);
    if (rc != 0 || is_close) return rc != 0 ? rc : -1;
    // root: collect xmlns declarations FIRST (needed to recognize rdf:RDF)
    for (auto &a : attrs) {
      if (a.name.substr(0, 6) == std::string_view("xmlns:")) {
        ns[std::string(a.name.substr(6))] = a.value;
      } else if (a.name == std::string_view("xmlns")) {
        return -2;  // default namespace: ElementTree fallback
      }
    }
    ns["xml"] = kXmlNs;  // implicit per XML spec
    if (!is_rdf(qname, "RDF")) return -2;  // single-node docs: fallback
    root_closed = self_close;
    return 0;
  }

  // Parse top-level node elements until ``end`` or the root close tag.
  // ``require_close``: reaching ``end`` without having seen </rdf:RDF> is
  // TRUNCATION (-1) — set for the whole-body parse and the final MT
  // chunk; interior chunks end at statement-aligned split points where
  // no close tag is expected.  (ElementTree raises on truncated docs;
  // silently loading a partial dataset would be worse than no fast path.)
  int parse_nodes(int64_t end, bool require_close) {
    while (true) {
      int rc = skip_misc();
      if (rc != 0) return rc;
      if (i >= end) return require_close ? -1 : 0;
      std::string_view qname;
      std::vector<Attr> attrs;
      bool self_close, is_close;
      int64_t save = i;
      rc = tag(qname, attrs, self_close, is_close);
      if (rc != 0) return rc;
      if (is_close) {
        return is_rdf(qname, "RDF") ? 0 : -1;
      }
      i = save;
      rc = node_element();
      if (rc != 0) return rc;
    }
  }

  int parse() {
    bool root_closed = false;
    int rc = parse_root(root_closed);
    if (rc != 0) return rc;
    if (root_closed) return 0;
    return parse_nodes(n, /*require_close=*/true);
  }

  int node_element() {
    std::string_view qname;
    std::vector<Attr> attrs;
    bool self_close, is_close;
    int rc = tag(qname, attrs, self_close, is_close);
    if (rc != 0 || is_close) return -1;
    // subject from rdf:about / rdf:ID / rdf:nodeID
    std::string subj;
    bool have_subj = false;
    for (auto &a : attrs) {
      if (is_rdf(a.name, "about")) {
        subj = a.value;
        have_subj = true;
      } else if (is_rdf(a.name, "ID")) {
        subj = "#" + a.value;
        have_subj = true;
      } else if (is_rdf(a.name, "nodeID")) {
        subj = "_:" + a.value;
        have_subj = true;
      }
    }
    if (!have_subj) return -2;  // fresh bnode numbering: Python fallback
    uint32_t subj_id = out->intern_view(subj);
    if (!is_rdf(qname, "Description")) {
      rc = expand(qname, scratch2);
      if (rc != 0) return rc;
      emit(subj_id, out->intern_view(kRdfNs + std::string("type")),
           out->intern_view(scratch2));
    }
    // non-rdf, non-xml attributes are literal properties
    for (auto &a : attrs) {
      size_t colon = a.name.find(':');
      if (colon == std::string_view::npos) continue;
      auto it = ns.find(std::string(a.name.substr(0, colon)));
      if (it == ns.end()) return -2;
      if (it->second == kRdfNs || it->second == kXmlNs) continue;
      scratch2.clear();
      scratch2.append(it->second);
      scratch2.append(a.name.substr(colon + 1));
      uint32_t p_id = out->intern_view(scratch2);
      scratch.clear();
      scratch.push_back('"');
      scratch.append(a.value);
      scratch.push_back('"');
      emit(subj_id, p_id, out->intern_view(scratch));
    }
    if (self_close) return 0;
    // property elements until the matching close tag
    std::string open_name(qname);
    while (true) {
      rc = skip_misc();
      if (rc != 0) return rc;
      int64_t save = i;
      std::string_view pq;
      std::vector<Attr> pattrs;
      bool psc, pclose;
      rc = tag(pq, pattrs, psc, pclose);
      if (rc != 0) return rc;
      if (pclose) {
        return pq == std::string_view(open_name) ? 0 : -1;
      }
      (void)save;
      rc = property_element(subj_id, pq, pattrs, psc);
      if (rc != 0) return rc;
    }
  }

  void emit(uint32_t s, uint32_t p, uint32_t o) {
    out->ids.push_back(s);
    out->ids.push_back(p);
    out->ids.push_back(o);
  }

  int property_element(uint32_t subj_id, std::string_view pq,
                       std::vector<Attr> &attrs, bool self_close) {
    int rc = expand(pq, scratch2);
    if (rc != 0) return rc;
    uint32_t p_id = out->intern_view(scratch2);
    const std::string *res = nullptr, *nid = nullptr, *dt = nullptr,
                      *lang = nullptr;
    for (auto &a : attrs) {
      if (is_rdf(a.name, "resource")) res = &a.value;
      else if (is_rdf(a.name, "nodeID")) nid = &a.value;
      else if (is_rdf(a.name, "datatype")) dt = &a.value;
      else if (a.name == std::string_view("xml:lang")) lang = &a.value;
      else return -2;  // parseType / reification / unknown: fallback
    }
    if (res != nullptr) {
      emit(subj_id, p_id, out->intern_view(*res));
      if (!self_close) {  // <p rdf:resource="..."></p> — empty content
        if (!close_empty(pq)) return -1;
      }
      return 0;
    }
    if (nid != nullptr) {
      scratch.clear();
      scratch.append("_:");
      scratch.append(*nid);
      emit(subj_id, p_id, out->intern_view(scratch));
      if (!self_close && !close_empty(pq)) return -1;
      return 0;
    }
    std::string text;
    if (!self_close) {
      int64_t t0 = i;
      while (i < n && d[i] != '<') i++;
      if (i >= n) return -1;
      if (i + 1 < n && d[i + 1] != '/') return -2;  // nested node element
      if (!unescape(d + t0, i - t0, text)) return -1;
      std::string_view cq;
      std::vector<Attr> ca;
      bool csc, cclose;
      if (tag(cq, ca, csc, cclose) != 0 || !cclose || cq != pq) return -1;
    }
    // strip (Python .strip()) the text content
    size_t b = 0, e = text.size();
    while (b < e && ws(text[b])) b++;
    while (e > b && ws(text[e - 1])) e--;
    scratch.clear();
    scratch.push_back('"');
    scratch.append(text, b, e - b);
    scratch.push_back('"');
    if (dt != nullptr && !dt->empty()) {
      scratch.append("^^");
      scratch.append(*dt);
    } else if (lang != nullptr && !lang->empty()) {
      scratch.push_back('@');
      scratch.append(*lang);
    }
    emit(subj_id, p_id, out->intern_view(scratch));
    return 0;
  }

  bool close_empty(std::string_view pq) {
    // expects optional whitespace then </pq>
    skip_ws();
    std::string_view cq;
    std::vector<Attr> ca;
    bool csc, cclose;
    if (tag(cq, ca, csc, cclose) != 0) return false;
    return cclose && cq == pq;
  }
};

int rx_parse_impl(const char *data, int64_t len, NtSession &out) {
  RxParser p;
  p.d = data;
  p.n = len;
  p.out = &out;
  return p.parse();
}

// Chunked multithreaded RDF/XML parse.  Within the supported subset (no
// nested node elements — those return -2 everywhere) a "</rdf:Description>"
// close can only occur at top level, so boundaries after it are
// statement-aligned; a split landing inside a comment or a typed-node
// body makes that chunk's parse FAIL, and ANY chunk failure falls back to
// the exact sequential parse (never to silently different triples).
int rx_parse_mt_impl(const char *data, int64_t len, int nthreads,
                     NtSession &out) {
  if (nthreads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nthreads = hc ? (int)hc : 1;
    const int64_t kMinChunk = 1 << 20;
    if ((int64_t)nthreads > len / kMinChunk) {
      nthreads = (int)(len / kMinChunk);
      if (nthreads < 1) nthreads = 1;
    }
  }
  if (nthreads > 16) nthreads = 16;
  if (nthreads <= 1) return rx_parse_impl(data, len, out);

  // Root prologue parsed once; chunks inherit the ns map.
  RxParser head;
  head.d = data;
  head.n = len;
  head.out = &out;
  bool root_closed = false;
  int rc = head.parse_root(root_closed);
  if (rc != 0) return rc;
  if (root_closed) return 0;
  int64_t body_start = head.i;

  static const char *kSplit = "</rdf:Description>";
  const size_t kSplitLen = 18;
  std::vector<int64_t> starts(nthreads + 1);
  starts[0] = body_start;
  starts[nthreads] = len;
  for (int t = 1; t < nthreads; t++) {
    int64_t target = body_start + (len - body_start) * t / nthreads;
    if (target < starts[t - 1]) target = starts[t - 1];
    const char *hit = (const char *)memmem(
        data + target, (size_t)(len - target), kSplit, kSplitLen);
    if (hit == nullptr) {
      // no further split points exist (typed-node-only documents have no
      // rdf:Description closes): don't rescan to EOF nthreads more times
      for (int u = t; u < nthreads; u++) starts[u] = len;
      break;
    }
    starts[t] = (hit - data) + (int64_t)kSplitLen;
  }
  if (starts[1] >= len) {
    return rx_parse_impl(data, len, out);  // < 2 real chunks: ST is faster
  }
  std::vector<NtSession> locals(nthreads);
  std::vector<int> rcs(nthreads, 0);
  std::vector<std::thread> workers;
  workers.reserve(nthreads);
  for (int t = 0; t < nthreads; t++) {
    if (starts[t] >= starts[t + 1]) continue;  // empty trailing chunk
    try {
      workers.emplace_back([&, t] {
        try {
          RxParser p;
          p.d = data;
          p.n = len;
          p.i = starts[t];
          p.out = &locals[t];
          p.ns = head.ns;
          // whichever chunk ends at EOF must witness </rdf:RDF>
          // (truncation guard); interior chunks end at split points
          rcs[t] = p.parse_nodes(starts[t + 1], starts[t + 1] == len);
        } catch (...) {
          rcs[t] = -3;
        }
      });
    } catch (const std::system_error &) {
      for (int u = t; u < nthreads; u++) rcs[u] = -3;
      break;
    }
  }
  for (auto &w : workers) w.join();
  for (int t = 0; t < nthreads; t++) {
    if (rcs[t] != 0) {
      // ANY chunk failure (mid-comment split, typed-node fragment,
      // unsupported construct) → exact sequential parse decides
      NtSession fresh;
      int rc2 = rx_parse_impl(data, len, fresh);
      if (rc2 == 0) out = std::move(fresh);
      return rc2;
    }
  }
  out = std::move(locals[0]);
  for (int t = 1; t < nthreads; t++) {
    NtSession &loc = locals[t];
    std::vector<uint32_t> remap(loc.terms.size() + 1);
    for (size_t k = 0; k < loc.terms.size(); k++) {
      remap[k + 1] = out.intern_view(
          std::string_view(loc.terms[k].first, loc.terms[k].second));
    }
    size_t base = out.ids.size();
    out.ids.resize(base + loc.ids.size());
    for (size_t k = 0; k < loc.ids.size(); k++) {
      out.ids[base + k] = remap[loc.ids[k]];
    }
  }
  return 0;
}

}  // namespace

// ────────────────────────────── C ABI ────────────────────────────────────

extern "C" {

// SDD
void *kn_sdd_new() { return new SddManager(); }
void kn_sdd_free(void *h) { delete (SddManager *)h; }

int64_t kn_sdd_new_var(void *h, double w_pos, double w_neg, int kind) {
  auto *m = (SddManager *)h;
  m->vars.push_back({w_pos, w_neg, kind});
  return (int64_t)m->vars.size() - 1;
}

void kn_sdd_set_weight(void *h, int64_t var, double w_pos, double w_neg) {
  auto *m = (SddManager *)h;
  m->vars[(size_t)var].w_pos = w_pos;
  m->vars[(size_t)var].w_neg = w_neg;
}

int64_t kn_sdd_literal(void *h, int64_t var, int positive) {
  auto *m = (SddManager *)h;
  return positive ? m->mk(var, TRUE_ID, FALSE_ID) : m->mk(var, FALSE_ID, TRUE_ID);
}

int64_t kn_sdd_apply(void *h, int64_t a, int64_t b, int op) {
  return ((SddManager *)h)->apply(a, b, op);
}

int64_t kn_sdd_negate(void *h, int64_t a) { return ((SddManager *)h)->negate(a); }

int64_t kn_sdd_exactly_one(void *h, const int64_t *vars, int64_t n) {
  auto *m = (SddManager *)h;
  int64_t result = FALSE_ID;
  for (int64_t ci = 0; ci < n; ci++) {
    int64_t term = TRUE_ID;
    for (int64_t vi = 0; vi < n; vi++) {
      term = m->apply(term, kn_sdd_literal(h, vars[vi], vars[vi] == vars[ci]), 0);
    }
    result = m->apply(result, term, 1);
  }
  return result;
}

// Vectorized apply: one library crossing for a whole derivation column
// (the per-call ctypes overhead dominates the reasoner's tag algebra
// otherwise — see provenance_seminaive's batched SDD round).
void kn_sdd_apply_batch(void *h, const int64_t *a, const int64_t *b,
                        int64_t n, int op, int64_t *out) {
  auto *m = (SddManager *)h;
  for (int64_t i = 0; i < n; i++) out[i] = m->apply(a[i], b[i], op);
}

// Segmented fold: out[gid[i]] = apply(out[gid[i]], tags[i]) in row order.
// Caller pre-initializes ``out`` to the fold identity (TRUE for 'and',
// FALSE for 'or').  Group ids need not be sorted.
void kn_sdd_reduce_groups(void *h, const int64_t *tags, const int64_t *gids,
                          int64_t n, int op, int64_t *out) {
  auto *m = (SddManager *)h;
  for (int64_t i = 0; i < n; i++) {
    int64_t g = gids[i];
    out[g] = m->apply(out[g], tags[i], op);
  }
}

double kn_sdd_wmc(void *h, int64_t nid) { return ((SddManager *)h)->wmc(nid); }

// ∂WMC/∂p per variable by weight substitution (diff_sdd.rs:15-46 semantics).
void kn_sdd_wmc_gradient(void *h, int64_t nid, const int64_t *vars, int64_t n,
                         double *out) {
  auto *m = (SddManager *)h;
  for (int64_t i = 0; i < n; i++) {
    size_t v = (size_t)vars[i];
    VarInfo saved = m->vars[v];
    m->vars[v] = {1.0, 0.0, saved.kind};
    double a = m->wmc(nid);
    m->vars[v] = {0.0, 1.0, saved.kind};
    double b = m->wmc(nid);
    m->vars[v] = saved;
    out[i] = saved.kind == 0 ? a - b : a;
  }
}

int64_t kn_sdd_size(void *h, int64_t nid) {
  auto *m = (SddManager *)h;
  if (nid == TRUE_ID || nid == FALSE_ID) return 0;
  std::vector<int64_t> stack{nid};
  std::unordered_map<int64_t, bool> seen;
  while (!stack.empty()) {
    int64_t n = stack.back();
    stack.pop_back();
    if (n == TRUE_ID || n == FALSE_ID || seen.count(n)) continue;
    seen[n] = true;
    stack.push_back(m->nodes[(size_t)n].hi);
    stack.push_back(m->nodes[(size_t)n].lo);
  }
  return (int64_t)seen.size();
}

int64_t kn_sdd_node_count(void *h) {
  return (int64_t)((SddManager *)h)->nodes.size();
}

// Model enumeration: paths to TRUE, DFS hi-before-lo (sdd.rs:661 semantics).
// Flattened output: per assignment pair (var, value); out_offsets has
// n_models+1 entries.  Returns the model count (≤ limit), or -1 if the
// flattened pairs exceed pair_cap (caller retries with a larger buffer).
int64_t kn_sdd_enumerate_models(void *h, int64_t nid, int64_t limit,
                                int64_t *out_vars, int8_t *out_vals,
                                int64_t pair_cap, int64_t *out_offsets) {
  auto *m = (SddManager *)h;
  int64_t n_models = 0, n_pairs = 0;
  std::vector<std::pair<int64_t, bool>> assignment;
  // explicit DFS: frame = (node, branch_state)
  struct Frame {
    int64_t node;
    int state;  // 0 = enter, 1 = after hi, 2 = after lo
  };
  std::vector<Frame> stack{{nid, 0}};
  out_offsets[0] = 0;
  while (!stack.empty() && n_models < limit) {
    Frame &f = stack.back();
    if (f.node == FALSE_ID) {
      stack.pop_back();
      continue;
    }
    if (f.node == TRUE_ID) {
      if (n_pairs + (int64_t)assignment.size() > pair_cap) return -1;
      for (auto &[v, val] : assignment) {
        out_vars[n_pairs] = v;
        out_vals[n_pairs] = val ? 1 : 0;
        n_pairs++;
      }
      out_offsets[++n_models] = n_pairs;
      stack.pop_back();
      continue;
    }
    const Node &n = m->nodes[(size_t)f.node];
    if (f.state == 0) {
      f.state = 1;
      assignment.emplace_back(n.var, true);
      stack.push_back({n.hi, 0});
    } else if (f.state == 1) {
      f.state = 2;
      assignment.back() = {n.var, false};
      stack.push_back({n.lo, 0});
    } else {
      assignment.pop_back();
      stack.pop_back();
    }
  }
  return n_models;
}

// N-Triples bulk parse
int64_t kn_nt_parse(const char *data, int64_t len, void **out_session) {
  auto *s = new NtSession();
  int rc = nt_parse_impl(data, len, *s);
  if (rc != 0) {
    delete s;
    *out_session = nullptr;
    return rc;
  }
  *out_session = s;
  return (int64_t)(s->ids.size() / 3);
}

// Multithreaded variant; nthreads <= 0 = auto (hardware concurrency).
int64_t kn_nt_parse_mt(const char *data, int64_t len, int nthreads,
                       void **out_session) {
  auto *s = new NtSession();
  int rc = nt_parse_mt_impl(data, len, nthreads, *s);
  if (rc != 0) {
    delete s;
    *out_session = nullptr;
    return rc;
  }
  *out_session = s;
  return (int64_t)(s->ids.size() / 3);
}

int64_t kn_nt_nterms(void *session) {
  return (int64_t)((NtSession *)session)->terms.size();
}

int64_t kn_nt_term_bytes(void *session) {
  return ((NtSession *)session)->term_bytes;
}

void kn_nt_ids(void *session, uint32_t *out) {
  auto *s = (NtSession *)session;
  std::memcpy(out, s->ids.data(), s->ids.size() * sizeof(uint32_t));
}

void kn_nt_terms(void *session, char *out, int64_t *offsets) {
  auto *s = (NtSession *)session;
  int64_t pos = 0;
  int64_t i = 0;
  for (auto &t : s->terms) {
    offsets[i++] = pos;
    std::memcpy(out + pos, t.first, t.second);
    pos += (int64_t)t.second;
  }
  offsets[i] = pos;
}

void kn_nt_free(void *session) { delete (NtSession *)session; }

// Turtle bulk parse.  prefix_blob: initial prefixes serialized as
// "pfx \x1F iri \x1E ..." (may be empty).  The returned session supports
// the kn_ttl_* accessors; term/id layout matches the NT session.
int64_t kn_ttl_parse_mt(const char *data, int64_t len, int nthreads,
                        const char *prefix_blob, int64_t prefix_len,
                        void **out_session) {
  auto *s = new TtlSession();
  TtlPrefixEnv env;
  int64_t p = 0;
  while (p < prefix_len) {
    int64_t sep = p;
    while (sep < prefix_len && prefix_blob[sep] != '\x1F') sep++;
    int64_t end = sep;
    while (end < prefix_len && prefix_blob[end] != '\x1E') end++;
    if (sep < end) {
      env.map[std::string(prefix_blob + p, (size_t)(sep - p))] =
          std::string(prefix_blob + sep + 1, (size_t)(end - sep - 1));
    }
    p = end + 1;
  }
  int rc;
  try {
    rc = ttl_parse_mt_impl(data, len, nthreads, env, s->nt);
  } catch (...) {
    rc = -3;
  }
  if (rc != 0) {
    delete s;
    *out_session = nullptr;
    return rc;
  }
  for (auto &kv : env.map) {
    s->prefix_blob.append(kv.first);
    s->prefix_blob.push_back('\x1F');
    s->prefix_blob.append(kv.second);
    s->prefix_blob.push_back('\x1E');
  }
  *out_session = s;
  return (int64_t)(s->nt.ids.size() / 3);
}

int64_t kn_ttl_nterms(void *session) {
  return (int64_t)((TtlSession *)session)->nt.terms.size();
}

int64_t kn_ttl_term_bytes(void *session) {
  return ((TtlSession *)session)->nt.term_bytes;
}

void kn_ttl_ids(void *session, uint32_t *out) {
  auto &s = ((TtlSession *)session)->nt;
  std::memcpy(out, s.ids.data(), s.ids.size() * sizeof(uint32_t));
}

void kn_ttl_terms(void *session, char *out, int64_t *offsets) {
  auto &s = ((TtlSession *)session)->nt;
  int64_t pos = 0;
  int64_t i = 0;
  for (auto &t : s.terms) {
    offsets[i++] = pos;
    std::memcpy(out + pos, t.first, t.second);
    pos += (int64_t)t.second;
  }
  offsets[i] = pos;
}

// RDF/XML bulk parse (streaming; chunk-parallel past ~1MB — see RxParser
// and rx_parse_mt_impl).  The session supports the kn_nt_* accessors
// (same NtSession layout).  nthreads <= 0 = auto.
int64_t kn_rx_parse_mt(const char *data, int64_t len, int nthreads,
                       void **out_session) {
  auto *s = new NtSession();
  int rc;
  try {
    rc = rx_parse_mt_impl(data, len, nthreads, *s);
  } catch (...) {
    rc = -3;
  }
  if (rc != 0) {
    delete s;
    *out_session = nullptr;
    return rc;
  }
  *out_session = s;
  return (int64_t)(s->ids.size() / 3);
}

int64_t kn_ttl_prefixes_len(void *session) {
  return (int64_t)((TtlSession *)session)->prefix_blob.size();
}

void kn_ttl_prefixes(void *session, char *out) {
  auto &b = ((TtlSession *)session)->prefix_blob;
  std::memcpy(out, b.data(), b.size());
}

void kn_ttl_free(void *session) { delete (TtlSession *)session; }

}  // extern "C"
