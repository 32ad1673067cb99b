// oe_serving.cc — native serving runtime (see oe_serving.h).
//
// Design: mmap the .npy files (zero copy-in, the OS pages rows on demand —
// the role the reference's in-RAM PS shards + zero-copy RpcView play for
// its serving cluster, server/RpcView.h), parse the two self-describing
// formats involved (model_meta JSON, numpy .npy headers) with small local
// parsers so the library has no dependencies beyond the C++17 standard
// library, and serve lookups lock-free (the maps are immutable after load).

#include "oe_serving.h"

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <cctype>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <memory>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

thread_local std::string g_error;

void set_error(const std::string& msg) { g_error = msg; }

// ---------------------------------------------------------------------------
// Minimal JSON parser (objects/arrays/strings/numbers/bools/null) — enough
// for model_meta, which this framework writes itself.
// ---------------------------------------------------------------------------
struct Json {
  enum Kind { kNull, kBool, kNum, kStr, kArr, kObj } kind = kNull;
  bool b = false;
  double num = 0;
  std::string str;
  std::vector<Json> arr;
  std::map<std::string, Json> obj;

  const Json* get(const std::string& key) const {
    auto it = obj.find(key);
    return it == obj.end() ? nullptr : &it->second;
  }
};

struct JsonParser {
  const char* p;
  const char* end;
  bool ok = true;

  // recursion bound: manifests/model_meta are untrusted bytes, and an
  // unbounded "[[[[..." nest overflows the parse stack (graftfuzz
  // manifest_json_garbage class) — far deeper than anything the
  // framework writes, well inside any sane thread stack
  static constexpr int kMaxDepth = 64;

  void skip() {
    while (p < end && std::isspace(static_cast<unsigned char>(*p))) ++p;
  }
  bool consume(char c) {
    skip();
    if (p < end && *p == c) { ++p; return true; }
    return false;
  }
  Json parse() { return parse_at(0); }
  Json parse_at(int depth) {
    skip();
    Json j;
    if (p >= end || depth > kMaxDepth) { ok = false; return j; }
    switch (*p) {
      case '{': {
        ++p;
        j.kind = Json::kObj;
        skip();
        if (consume('}')) return j;
        do {
          skip();
          Json key = parse_string();
          if (!ok || !consume(':')) { ok = false; return j; }
          j.obj[key.str] = parse_at(depth + 1);
        } while (ok && consume(','));
        if (!consume('}')) ok = false;
        return j;
      }
      case '[': {
        ++p;
        j.kind = Json::kArr;
        skip();
        if (consume(']')) return j;
        do {
          j.arr.push_back(parse_at(depth + 1));
        } while (ok && consume(','));
        if (!consume(']')) ok = false;
        return j;
      }
      case '"':
        return parse_string();
      case 't':
        if (end - p >= 4 && !std::strncmp(p, "true", 4)) {
          p += 4; j.kind = Json::kBool; j.b = true; return j;
        }
        ok = false; return j;
      case 'f':
        if (end - p >= 5 && !std::strncmp(p, "false", 5)) {
          p += 5; j.kind = Json::kBool; return j;
        }
        ok = false; return j;
      case 'n':
        if (end - p >= 4 && !std::strncmp(p, "null", 4)) { p += 4; return j; }
        ok = false; return j;
      default: {
        char* num_end = nullptr;
        j.num = std::strtod(p, &num_end);
        if (num_end == p || num_end > end) { ok = false; return j; }
        j.kind = Json::kNum;
        p = num_end;
        return j;
      }
    }
  }
  Json parse_string() {
    Json j;
    skip();
    if (p >= end || *p != '"') { ok = false; return j; }
    ++p;
    j.kind = Json::kStr;
    while (p < end && *p != '"') {
      if (*p == '\\' && p + 1 < end) {
        ++p;
        switch (*p) {
          case 'n': j.str += '\n'; break;
          case 't': j.str += '\t'; break;
          case 'r': j.str += '\r'; break;
          case 'u':  // checkpoint names are ascii; keep escapes verbatim
            j.str += "\\u";
            break;
          default: j.str += *p;
        }
      } else {
        j.str += *p;
      }
      ++p;
    }
    if (p >= end) { ok = false; return j; }
    ++p;
    return j;
  }
};

// Untrusted JSON numbers -> integers: a double outside int64's range
// (or NaN) makes the straight static_cast undefined behavior
// (float-cast-overflow; UBSan aborts) — clamp-refuse instead. The
// bound is the largest double below 2^63; the comparison is written so
// NaN falls through to false.
bool json_i64(const Json* j, int64_t* out) {
  if (!j || j->kind != Json::kNum) return false;
  double v = j->num;
  if (!(v >= -9.223372036854775e18 && v <= 9.223372036854775e18))
    return false;
  *out = static_cast<int64_t>(v);
  return true;
}

bool json_int(const Json* j, int* out) {
  int64_t v;
  if (!json_i64(j, &v) || v < INT32_MIN || v > INT32_MAX) return false;
  *out = static_cast<int>(v);
  return true;
}

bool read_file(const std::string& path, std::string* out) {
  FILE* f = std::fopen(path.c_str(), "rb");
  if (!f) return false;
  std::fseek(f, 0, SEEK_END);
  long n = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  out->resize(n < 0 ? 0 : static_cast<size_t>(n));
  size_t got = n > 0 ? std::fread(&(*out)[0], 1, out->size(), f) : 0;
  std::fclose(f);
  return got == out->size();
}

// ---------------------------------------------------------------------------
// Memory-mapped .npy array (v1.0/2.0 headers, C-order little-endian).
// The same parser reads standalone .npy files (mmap'd whole) and npz
// MEMBERS (views into a mapped delta payload owned by the model).
// ---------------------------------------------------------------------------
struct NpyArray {
  void* map = nullptr;          // owned mapping (null for npz views)
  size_t map_size = 0;
  const char* data = nullptr;   // first element
  std::string dtype;            // e.g. "<f4", "<i8"
  size_t itemsize = 0;
  std::vector<int64_t> shape;

  ~NpyArray() {
    if (map) ::munmap(map, map_size);
  }
  int64_t rows() const { return shape.empty() ? 0 : shape[0]; }
  int64_t row_elems() const {
    int64_t n = 1;
    for (size_t i = 1; i < shape.size(); ++i) n *= shape[i];
    return n;
  }
};

// Parse one .npy image at [b, b+size) into arr (data points INTO the
// buffer; arr does not own it). False + set_error on damage.
bool parse_npy(const unsigned char* b, size_t size, NpyArray* arr,
               const std::string& what) {
  if (size < 10 || std::memcmp(b, "\x93NUMPY", 6) != 0) {
    set_error("not a .npy image: " + what);
    return false;
  }
  int major = b[6];
  size_t header_len, header_off;
  if (major == 1) {
    header_len = b[8] | (b[9] << 8);
    header_off = 10;
  } else {
    if (size < 12) {
      set_error("corrupt .npy header in " + what);
      return false;
    }
    header_len = b[8] | (b[9] << 8) | (b[10] << 16)
        | (static_cast<size_t>(b[11]) << 24);
    header_off = 12;
  }
  if (header_off + header_len > size) {
    set_error("corrupt .npy header in " + what);
    return false;
  }
  std::string header(reinterpret_cast<const char*>(b + header_off),
                     header_len);
  // parse "{'descr': '<f4', 'fortran_order': False, 'shape': (8, 4), }"
  auto find_val = [&](const std::string& key) -> std::string {
    size_t k = header.find("'" + key + "'");
    if (k == std::string::npos) return "";
    size_t c = header.find(':', k);
    if (c == std::string::npos) return "";
    size_t s = c + 1;
    while (s < header.size() && header[s] == ' ') ++s;
    size_t e = s;
    if (header[s] == '\'') {
      e = header.find('\'', s + 1);
      return header.substr(s + 1, e - s - 1);
    }
    if (header[s] == '(') {
      e = header.find(')', s);
      return header.substr(s, e - s + 1);
    }
    while (e < header.size() && header[e] != ',' && header[e] != '}') ++e;
    return header.substr(s, e - s);
  };
  arr->dtype = find_val("descr");
  if (find_val("fortran_order").find("True") != std::string::npos) {
    set_error("fortran-order arrays unsupported: " + what);
    return false;
  }
  arr->shape.clear();
  std::string shape = find_val("shape");
  const char* sp = shape.c_str();
  while (*sp) {
    if (std::isdigit(static_cast<unsigned char>(*sp))) {
      arr->shape.push_back(std::strtoll(sp, const_cast<char**>(&sp), 10));
    } else {
      ++sp;
    }
  }
  if (arr->dtype.size() < 3) {
    set_error("bad dtype in " + what);
    return false;
  }
  arr->itemsize = std::strtoul(arr->dtype.c_str() + 2, nullptr, 10);
  arr->data = reinterpret_cast<const char*>(b + header_off + header_len);
  // a truncated file (disk-full / killed writer) must fail the LOAD, not
  // SIGSEGV the serving process at the first past-the-end lookup; the
  // element count is computed with overflow-checked multiplication so a
  // corrupt header with huge dims cannot wrap `need` past the check
  size_t need = arr->itemsize;
  for (int64_t d : arr->shape) {
    if (d < 0 ||
        __builtin_mul_overflow(need, static_cast<size_t>(d), &need) ||
        need > size) {
      set_error("corrupt .npy shape in " + what);
      return false;
    }
  }
  if (header_off + header_len + need > size) {
    set_error("truncated .npy data in " + what);
    return false;
  }
  return true;
}

std::unique_ptr<NpyArray> open_npy(const std::string& path) {
  int fd = ::open(path.c_str(), O_RDONLY);
  if (fd < 0) {
    set_error("cannot open " + path);
    return nullptr;
  }
  struct stat st;
  if (::fstat(fd, &st) != 0 || st.st_size < 10) {
    ::close(fd);
    set_error("cannot stat " + path);
    return nullptr;
  }
  auto arr = std::make_unique<NpyArray>();
  arr->map_size = static_cast<size_t>(st.st_size);
  arr->map = ::mmap(nullptr, arr->map_size, PROT_READ, MAP_SHARED, fd, 0);
  ::close(fd);
  if (arr->map == MAP_FAILED) {
    arr->map = nullptr;
    set_error("mmap failed for " + path);
    return nullptr;
  }
  if (!parse_npy(static_cast<const unsigned char*>(arr->map),
                 arr->map_size, arr.get(), path)) {
    return nullptr;
  }
  return arr;
}

bool weights_dtype_supported(const NpyArray& a) {
  char c = a.dtype[1];
  // f4/f8, plus bfloat16 (numpy writes ml_dtypes bfloat16 as '<V2')
  return (c == 'f' && (a.itemsize == 4 || a.itemsize == 8))
      || (c == 'V' && a.itemsize == 2);
}

float load_elem_as_float(const NpyArray& a, int64_t idx) {
  const char* p = a.data + idx * a.itemsize;
  char c = a.dtype[1];
  if (c == 'f' && a.itemsize == 4) {
    float v;
    std::memcpy(&v, p, 4);
    return v;
  }
  if (c == 'f' && a.itemsize == 8) {
    double v;
    std::memcpy(&v, p, 8);
    return static_cast<float>(v);
  }
  if (c == 'V' && a.itemsize == 2) {  // bfloat16: high 16 bits of an f32
    uint16_t h;
    std::memcpy(&h, p, 2);
    uint32_t bits = static_cast<uint32_t>(h) << 16;
    float v;
    std::memcpy(&v, &bits, 4);
    return v;
  }
  return 0.0f;
}

bool is_wide_keys(const NpyArray& a) {
  // wide (x64-off) hash dumps store keys as [n, 2] int32 (lo, hi) pairs
  return a.shape.size() == 2 && a.shape[1] == 2 && a.itemsize == 4;
}

bool keys_dtype_supported(const NpyArray& a) {
  // key/id/chunk columns: [n] i4/i8 (or u4/u8) — or the wide [n, 2]
  // int32 pair layout. load_key_as_i64 memcpy's 4 or 8 bytes per row;
  // any other dtype/shape would read the WRONG bytes (a '<i2' keys
  // member reads past its own rows into the neighbouring member —
  // silent key garbage, silent Python-vs-native divergence), so it
  // must refuse here, before the first key load
  if (is_wide_keys(a)) return true;
  if (a.shape.size() != 1 || a.dtype.size() < 3) return false;
  char c = a.dtype[1];
  return (c == 'i' || c == 'u') && (a.itemsize == 4 || a.itemsize == 8);
}

int64_t load_key_as_i64(const NpyArray& a, int64_t idx) {
  // row-indexed key load: [n] int32/int64, or [n, 2] int32 pairs joined
  // to the 64-bit value ((hi << 32) | unsigned lo)
  if (is_wide_keys(a)) {
    const char* p = a.data + idx * 2 * a.itemsize;
    int32_t lo, hi;
    std::memcpy(&lo, p, 4);
    std::memcpy(&hi, p + 4, 4);
    // shift in unsigned space: a signed left shift of a negative hi word
    // is UB under -std=c++17
    uint64_t u = (static_cast<uint64_t>(static_cast<uint32_t>(hi)) << 32)
        | static_cast<uint32_t>(lo);
    return static_cast<int64_t>(u);
  }
  const char* p = a.data + idx * a.itemsize;
  if (a.itemsize == 4) {
    int32_t v;
    std::memcpy(&v, p, 4);
    return v;
  }
  int64_t v;
  std::memcpy(&v, p, 8);
  return v;
}

// ---------------------------------------------------------------------------
// crc32 (zlib polynomial) — the delta manifest's whole-file checksums
// are verified before any byte of a delta payload is trusted, matching
// checkpoint_delta.verify_chain.
// ---------------------------------------------------------------------------
struct Crc32Table {
  uint32_t t[256];
  Crc32Table() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};

uint32_t crc32_update(uint32_t crc, const unsigned char* buf, size_t len) {
  // zlib.crc32(data, prev) semantics: chainable over field slices (the
  // per-chunk checksums crc field A then field B with one running crc)
  // magic static: C++11 guarantees thread-safe one-time construction
  // (two threads loading delta dirs concurrently must never read a
  // half-built table — a wrong crc would misclassify a valid delta
  // as torn)
  static const Crc32Table table;
  uint32_t c = crc ^ 0xFFFFFFFFu;
  for (size_t i = 0; i < len; ++i)
    c = table.t[(c ^ buf[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

uint32_t crc32_of(const unsigned char* buf, size_t len) {
  return crc32_update(0, buf, len);
}

// A whole file mmap'd read-only; delta payloads stay mapped for the
// model's lifetime (their rows serve directly from the mapping).
struct MappedFile {
  void* map = nullptr;
  size_t size = 0;

  ~MappedFile() {
    if (map) ::munmap(map, size);
  }
  const unsigned char* bytes() const {
    return static_cast<const unsigned char*>(map);
  }
};

std::unique_ptr<MappedFile> map_file(const std::string& path) {
  int fd = ::open(path.c_str(), O_RDONLY);
  if (fd < 0) {
    set_error("cannot open " + path);
    return nullptr;
  }
  struct stat st;
  if (::fstat(fd, &st) != 0) {
    ::close(fd);
    set_error("cannot stat " + path);
    return nullptr;
  }
  auto mf = std::make_unique<MappedFile>();
  mf->size = static_cast<size_t>(st.st_size);
  mf->map = ::mmap(nullptr, mf->size ? mf->size : 1, PROT_READ,
                   MAP_SHARED, fd, 0);
  ::close(fd);
  if (mf->map == MAP_FAILED) {
    mf->map = nullptr;
    set_error("mmap failed for " + path);
    return nullptr;
  }
  return mf;
}

// ---------------------------------------------------------------------------
// npz (zip) member table — delta payloads are np.savez archives of
// STORED .npy members (save_delta's default; compressed-at-rest delta
// chains are refused with a clear message — the native reader trades
// codec support for zero dependencies). Offsets are resolved through
// the central directory, whose sizes are authoritative.
// ---------------------------------------------------------------------------
uint32_t rd32(const unsigned char* p) {
  return p[0] | (p[1] << 8) | (p[2] << 16)
      | (static_cast<uint32_t>(p[3]) << 24);
}
uint16_t rd16(const unsigned char* p) { return p[0] | (p[1] << 8); }

struct ZipMember {
  size_t offset = 0;   // first data byte
  size_t size = 0;     // uncompressed == stored size
};

bool parse_npz(const unsigned char* b, size_t n, const std::string& what,
               std::map<std::string, ZipMember>* out) {
  // find the end-of-central-directory record in the trailing 64 KiB
  if (n < 22) {
    set_error("truncated npz: " + what);
    return false;
  }
  size_t scan_from = n >= (1 << 16) + 22 ? n - ((1 << 16) + 22) : 0;
  size_t eocd = std::string::npos;
  for (size_t i = n - 22 + 1; i-- > scan_from;) {
    if (b[i] == 0x50 && b[i + 1] == 0x4b && b[i + 2] == 0x05
        && b[i + 3] == 0x06) {
      eocd = i;
      break;
    }
  }
  if (eocd == std::string::npos) {
    set_error("npz central directory not found: " + what);
    return false;
  }
  uint16_t entries = rd16(b + eocd + 10);
  uint32_t cd_off = rd32(b + eocd + 16);
  size_t p = cd_off;
  for (uint16_t e = 0; e < entries; ++e) {
    if (p + 46 > n || rd32(b + p) != 0x02014b50) {
      set_error("corrupt npz central directory: " + what);
      return false;
    }
    uint16_t method = rd16(b + p + 10);
    uint32_t csize = rd32(b + p + 20);
    uint32_t usize = rd32(b + p + 24);
    uint16_t name_len = rd16(b + p + 28);
    uint16_t extra_len = rd16(b + p + 30);
    uint16_t comment_len = rd16(b + p + 32);
    uint32_t lho = rd32(b + p + 42);
    // bound the variable-length tail BEFORE reading the name: a
    // corrupt name_len near the end of the mapping must error, not
    // walk past it
    if (p + 46u + name_len + extra_len + comment_len > n) {
      set_error("corrupt npz central directory: " + what);
      return false;
    }
    std::string name(reinterpret_cast<const char*>(b + p + 46), name_len);
    if (csize == 0xFFFFFFFFu || usize == 0xFFFFFFFFu
        || lho == 0xFFFFFFFFu) {
      set_error("zip64 npz member unsupported: " + what + ":" + name);
      return false;
    }
    if (method != 0) {
      set_error("deflated npz member " + name + " in " + what
                + " — the native reader serves uncompressed delta "
                  "payloads (save deltas with compress='' or compact "
                  "the chain)");
      return false;
    }
    // size_t BEFORE the add: a near-max uint32 offset must fail the
    // bound, not wrap past it into an out-of-bounds read
    if (static_cast<size_t>(lho) + 30 > n || rd32(b + lho) != 0x04034b50) {
      set_error("corrupt npz local header: " + what + ":" + name);
      return false;
    }
    // the LOCAL header's name/extra lengths position the data (the
    // central copy may record different extra bytes)
    uint16_t lnl = rd16(b + lho + 26);
    uint16_t lxl = rd16(b + lho + 28);
    size_t data = static_cast<size_t>(lho) + 30 + lnl + lxl;
    if (data + usize > n) {
      set_error("truncated npz member " + name + " in " + what);
      return false;
    }
    (*out)[name] = ZipMember{data, usize};
    p += 46u + name_len + extra_len + comment_len;
  }
  return true;
}

}  // namespace

// ---------------------------------------------------------------------------
// Public handles
// ---------------------------------------------------------------------------
struct oe_variable {
  std::string name;
  int variable_id = 0;
  int dim = 0;
  int64_t vocab = 0;      // -1 => hash
  // one entry per dump part (single-host dumps have one); multi-host
  // bounded parts carry keyed (ids, rows) files like hash parts —
  // delta payloads append further parts (views into mapped npz files)
  std::vector<std::unique_ptr<NpyArray>> weights;
  std::vector<std::unique_ptr<NpyArray>> keys;  // hash keys / bounded ids
  bool direct = false;  // single dense part: row == id, no index
  // key/id -> (part << 40 | row); parts < 2^24, rows < 2^40
  std::unordered_map<int64_t, int64_t> index;
  // delta redirects for DIRECT variables (id -> part|row): checked
  // before the base row so newest-wins replay needs no base rewrite;
  // indexed variables take delta rows straight into `index`
  std::unordered_map<int64_t, int64_t> overlay;
  int64_t total_rows = 0;
};

struct oe_model {
  std::string sign;
  std::vector<std::unique_ptr<oe_variable>> variables;
  std::unordered_map<std::string, oe_variable*> by_name;
  std::unordered_map<int, oe_variable*> by_id;
  // delta-chain seq the load replayed up to (applied_seq semantics)
  int64_t version = 0;
  // mapped delta payload files: their member arrays serve rows for the
  // model's whole lifetime
  std::vector<std::unique_ptr<MappedFile>> payloads;
};

namespace {

// resolve one 64-bit key to (part, row) or row -1 (zero row)
inline int64_t resolve_row(const oe_variable* var, int64_t key,
                           int64_t* part) {
  constexpr int64_t kRowMask = (int64_t(1) << 40) - 1;
  if (var->direct) {
    if (key < 0 || key >= var->vocab) return -1;
    if (!var->overlay.empty()) {
      auto it = var->overlay.find(key);
      if (it != var->overlay.end()) {
        *part = it->second >> 40;
        return it->second & kRowMask;
      }
    }
    *part = 0;
    return key;
  }
  if (var->vocab >= 0 && (key < 0 || key >= var->vocab)) return -1;
  auto it = var->index.find(key);
  if (it == var->index.end()) return -1;
  *part = it->second >> 40;
  return it->second & kRowMask;
}

inline void copy_row(const oe_variable* var, int64_t part, int64_t row,
                     float* dst) {
  const int dim = var->dim;
  if (row < 0) {
    std::memset(dst, 0, sizeof(float) * dim);
    return;
  }
  const NpyArray& w = *var->weights[part];
  if (w.dtype[1] == 'f' && w.itemsize == 4) {
    std::memcpy(dst, w.data + row * dim * 4, sizeof(float) * dim);
  } else {
    for (int d = 0; d < dim; ++d) {
      dst[d] = load_elem_as_float(w, row * dim + d);
    }
  }
}

bool npy_scalar_i64(const NpyArray& a, int64_t* out) {
  if (!a.shape.empty() || a.itemsize != 8 || a.dtype[1] != 'i')
    return false;
  std::memcpy(out, a.data, 8);
  return true;
}

// One verified delta payload for one variable, parsed into npy views
// over the mapped npz bytes.
struct DeltaPayload {
  std::string name;
  std::map<std::string, ZipMember> members;
  const unsigned char* base = nullptr;

  bool view(const std::string& member, NpyArray* out,
            const std::string& what) const {
    auto it = members.find(member + ".npy");
    if (it == members.end()) {
      set_error("delta payload missing member " + member + ": " + what);
      return false;
    }
    return parse_npy(base + it->second.offset, it->second.size, out,
                     what + ":" + member);
  }
};

// Mirror checkpoint_delta._verify_array_chunks: recompute each chunk's
// crc32 over the payload's field rows in _field_order (weights, then
// slot_* sorted — array payloads carry no keys) and compare against
// the manifest entry's chunk_crc list. The whole-file crc has already
// matched by the time this runs, so a mismatch means the manifest and
// the member bytes disagree (crc swaps, crc-preserving payload swaps);
// the Python verifier treats that as tear damage and the caller here
// applies the same final-drop/mid-fail semantics. Returns false on any
// mismatch or ill-formed geometry; never reads out of bounds.
bool verify_chunk_crcs(const DeltaPayload& pl, const Json& chunk_crc,
                       const std::string& what) {
  NpyArray chunks, rpc, vocab;
  int64_t R = 0, V = 0;
  constexpr int64_t kMaxRows = int64_t(1) << 56;
  if (!pl.view("chunks", &chunks, what)
      || !pl.view("rows_per_chunk", &rpc, what)
      || !pl.view("vocab", &vocab, what)
      || !npy_scalar_i64(rpc, &R) || !npy_scalar_i64(vocab, &V)
      || R <= 0 || R > kMaxRows || V < 0 || V > kMaxRows
      || !keys_dtype_supported(chunks) || is_wide_keys(chunks)
      || static_cast<int64_t>(chunk_crc.arr.size()) != chunks.rows()) {
    return false;
  }
  const int64_t nchunks = (V + R - 1) / R;
  // _field_order: weights first, then slot_* sorted (pl.members is a
  // sorted map, so slot members come out in field order already)
  std::vector<std::string> order = {"weights"};
  for (const auto& m : pl.members) {
    if (m.first.rfind("slot_", 0) == 0 && m.first.size() > 4
        && m.first.compare(m.first.size() - 4, 4, ".npy") == 0) {
      order.push_back(m.first.substr(0, m.first.size() - 4));
    }
  }
  int64_t off = 0;
  for (size_t i = 0; i < chunk_crc.arr.size(); ++i) {
    int64_t want = 0;
    if (!json_i64(&chunk_crc.arr[i], &want)) return false;
    int64_t c = load_key_as_i64(chunks, static_cast<int64_t>(i));
    if (c < 0 || c >= nchunks) return false;
    int64_t n = std::min((c + 1) * R, V) - c * R;
    uint32_t crc = 0;
    for (const std::string& f : order) {
      NpyArray a;
      if (!pl.view(f, &a, what)) return false;
      int64_t rowbytes = a.row_elems()
          * static_cast<int64_t>(a.itemsize);
      if (rowbytes < 0 || off + n > a.rows()) return false;
      crc = crc32_update(
          crc,
          reinterpret_cast<const unsigned char*>(a.data)
              + off * rowbytes,
          static_cast<size_t>(n) * static_cast<size_t>(rowbytes));
    }
    if (crc != static_cast<uint32_t>(want)) return false;
    off += n;
  }
  for (const std::string& f : order) {
    NpyArray a;
    if (!pl.view(f, &a, what) || a.rows() != off) return false;
  }
  return true;
}

// Mirror checkpoint_delta._verify_array_blocks (delta format 2): one
// crc32 a block of block_rows payload rows (the last block short), over
// the field rows in _field_order, against the manifest record's
// block_crc list. Same contract as verify_chunk_crcs: false on any
// mismatch or ill-formed geometry, never out of bounds.
bool verify_block_crcs(const DeltaPayload& pl, const Json& block_crc,
                       const Json* block_rows, const std::string& what) {
  int64_t B = 0;
  NpyArray w;
  if (!json_i64(block_rows, &B) || B <= 0
      || !pl.view("weights", &w, what)) {
    return false;
  }
  const int64_t rows = w.rows();
  if (static_cast<int64_t>(block_crc.arr.size()) != (rows + B - 1) / B) {
    return false;
  }
  std::vector<std::string> order = {"weights"};
  for (const auto& m : pl.members) {
    if (m.first.rfind("slot_", 0) == 0 && m.first.size() > 4
        && m.first.compare(m.first.size() - 4, 4, ".npy") == 0) {
      order.push_back(m.first.substr(0, m.first.size() - 4));
    }
  }
  std::vector<NpyArray> fields(order.size());
  for (size_t k = 0; k < order.size(); ++k) {
    if (!pl.view(order[k], &fields[k], what) || fields[k].rows() != rows
        || fields[k].row_elems() < 0) {
      return false;
    }
  }
  for (size_t i = 0; i < block_crc.arr.size(); ++i) {
    int64_t want = 0;
    if (!json_i64(&block_crc.arr[i], &want)) return false;
    const int64_t off = static_cast<int64_t>(i) * B;
    const int64_t n = std::min(off + B, rows) - off;
    uint32_t crc = 0;
    for (const NpyArray& a : fields) {
      int64_t rowbytes = a.row_elems() * static_cast<int64_t>(a.itemsize);
      crc = crc32_update(
          crc,
          reinterpret_cast<const unsigned char*>(a.data) + off * rowbytes,
          static_cast<size_t>(n) * static_cast<size_t>(rowbytes));
    }
    if (crc != static_cast<uint32_t>(want)) return false;
  }
  return true;
}

// Apply one variable's verified payload newest-wins: its weights become
// a new part; overlay/index entries redirect the touched keys to it.
bool apply_delta_payload(oe_variable* var, const DeltaPayload& pl,
                         const std::string& what) {
  auto w = std::make_unique<NpyArray>();
  if (!pl.view("weights", w.get(), what)) return false;
  if (w->row_elems() != var->dim) {
    set_error("delta weights dim mismatch for " + var->name + ": "
              + what);
    return false;
  }
  if (!weights_dtype_supported(*w)) {
    set_error("unsupported delta weights dtype " + w->dtype + ": "
              + what);
    return false;
  }
  const int64_t part = static_cast<int64_t>(var->weights.size());
  const int64_t wrows = w->rows();
  if (pl.members.count("keys.npy")) {           // hash payload
    NpyArray keys;
    if (!pl.view("keys", &keys, what)) return false;
    if (!keys_dtype_supported(keys)) {
      set_error("unsupported delta key dtype " + keys.dtype + " for "
                + var->name + ": " + what);
      return false;
    }
    if (keys.rows() != wrows) {
      set_error("delta key/row count mismatch for " + var->name + ": "
                + what);
      return false;
    }
    if (var->direct) {
      set_error("hash delta payload for bounded variable " + var->name
                + ": " + what);
      return false;
    }
    for (int64_t j = 0; j < wrows; ++j) {
      int64_t k64 = load_key_as_i64(keys, j);
      auto ins = var->index.insert({k64, (part << 40) | j});
      if (ins.second) {
        ++var->total_rows;                       // brand-new key
      } else {
        ins.first->second = (part << 40) | j;    // newest wins
      }
    }
  } else {                                       // array (chunked) payload
    NpyArray chunks, rpc, vocab;
    int64_t R = 0, V = 0;
    if (!pl.view("chunks", &chunks, what)
        || !pl.view("rows_per_chunk", &rpc, what)
        || !pl.view("vocab", &vocab, what)) {
      return false;
    }
    // R/V sanity bounds keep every derived quantity ((chunk+1)*R,
    // V+R-1) inside int64 — a hostile rows_per_chunk near 2^63 would
    // otherwise signed-overflow (UB) before any range check can fire
    constexpr int64_t kMaxRows = int64_t(1) << 56;
    if (!npy_scalar_i64(rpc, &R) || !npy_scalar_i64(vocab, &V)
        || R <= 0 || R > kMaxRows || V < 0 || V > kMaxRows) {
      set_error("corrupt array delta header for " + var->name + ": "
                + what);
      return false;
    }
    if (!keys_dtype_supported(chunks) || is_wide_keys(chunks)) {
      set_error("unsupported delta chunk-id dtype " + chunks.dtype
                + " for " + var->name + ": " + what);
      return false;
    }
    const int64_t nchunks = (V + R - 1) / R;
    auto& target = var->direct ? var->overlay : var->index;
    int64_t j = 0;
    for (int64_t c = 0; c < chunks.rows(); ++c) {
      int64_t chunk = load_key_as_i64(chunks, c);
      if (chunk < 0 || chunk >= nchunks) {
        set_error("array delta chunk id out of range for " + var->name
                  + ": " + what);
        return false;
      }
      int64_t l1 = std::min((chunk + 1) * R, V);
      for (int64_t g = chunk * R; g < l1; ++g, ++j) {
        if (j >= wrows) {
          set_error("array delta rows short for " + var->name + ": "
                    + what);
          return false;
        }
        target[g] = (part << 40) | j;
      }
    }
    if (j != wrows) {
      set_error("array delta rows mismatch for " + var->name + ": "
                + what);
      return false;
    }
  }
  var->weights.push_back(std::move(w));
  return true;
}

// Resolve the delta_manifest chain over a freshly loaded base —
// checkpoint_delta.verify_chain + replay_chain semantics: every
// committed entry crc-verified whole, replayed in order; a torn/missing
// FINAL entry is discarded (recover to the last complete delta), torn
// MIDDLE fails the load. Returns false only on a load-fatal condition.
bool replay_delta_chain(oe_model* model, const std::string& root) {
  struct stat st;
  std::string mpath = root + "/delta_manifest";
  if (::stat(mpath.c_str(), &st) != 0) return true;  // plain full dump
  std::string text;
  if (!read_file(mpath, &text)) {
    set_error("cannot read " + mpath);
    return false;
  }
  JsonParser jp{text.c_str(), text.c_str() + text.size()};
  Json manifest = jp.parse();
  if (!jp.ok || manifest.kind != Json::kObj) {
    set_error("delta_manifest is not valid JSON: " + mpath);
    return false;
  }
  int64_t fmt_num = -1;
  // format 2 adds block_crc records, format 3 hash records exact to the
  // key (keys + weights + slots, no chunk members: what a hash payload
  // is read by here anyway); older manifests read as before
  if (!json_i64(manifest.get("format"), &fmt_num)
      || fmt_num < 1 || fmt_num > 3) {
    set_error("unknown delta manifest format at " + root);
    return false;
  }
  if (const Json* cs = manifest.get("content_seq")) {
    if (!json_i64(cs, &model->version)) {
      set_error("corrupt content_seq in delta manifest at " + root);
      return false;
    }
  }
  const Json* chain = manifest.get("chain");
  if (!chain || chain->kind != Json::kArr) return true;
  for (size_t i = 0; i < chain->arr.size(); ++i) {
    const Json& entry = chain->arr[i];
    const Json* vars = entry.get("vars");
    int64_t seq64 = 0;
    if (!vars || vars->kind != Json::kObj
        || !json_i64(entry.get("seq"), &seq64)) {
      set_error("corrupt delta chain entry at " + root);
      return false;
    }
    // verify the WHOLE entry before applying any of it (a bad file
    // discards/refuses the entry as a unit, like verify_chain)
    std::vector<std::unique_ptr<MappedFile>> maps;
    std::vector<DeltaPayload> payloads;
    bool bad = false;
    for (const auto& kv : vars->obj) {
      const Json* file = kv.second.get("file");
      int64_t crc64 = 0;
      if (!file || file->kind != Json::kStr
          || !json_i64(kv.second.get("crc32"), &crc64)) {
        bad = true;                      // malformed var record: tear
        break;
      }
      auto mf = map_file(root + "/" + file->str);
      if (!mf
          || crc32_of(mf->bytes(), mf->size)
              != static_cast<uint32_t>(crc64)) {
        bad = true;                      // missing or corrupt bytes
        break;
      }
      DeltaPayload pl;
      pl.name = kv.first;
      pl.base = mf->bytes();
      if (!parse_npz(pl.base, mf->size, file->str, &pl.members)) {
        // crc MATCHED, so these are exactly the committed bytes — a
        // parse failure is an unsupported feature (deflate/zip64), not
        // a tear: fail loudly instead of "recovering" past real data
        return false;
      }
      // per-chunk checksums, when the manifest carries them, must
      // re-verify just like checkpoint_delta.verify_chain — a manifest
      // that lies about its chunk crcs (crc swap, crc-preserving
      // payload swap) is tear damage in BOTH readers, or the two would
      // silently recover to different versions
      const Json* ccrc = kv.second.get("chunk_crc");
      if (ccrc && ccrc->kind != Json::kNull
          && (ccrc->kind != Json::kArr
              || !verify_chunk_crcs(pl, *ccrc, file->str))) {
        bad = true;                      // chunk checksum mismatch
        break;
      }
      const Json* bcrc = kv.second.get("block_crc");
      if (bcrc && bcrc->kind != Json::kNull
          && (bcrc->kind != Json::kArr
              || !verify_block_crcs(pl, *bcrc,
                                    kv.second.get("block_rows"),
                                    file->str))) {
        bad = true;                      // block checksum mismatch
        break;
      }
      maps.push_back(std::move(mf));
      payloads.push_back(std::move(pl));
    }
    if (bad) {
      if (i + 1 == chain->arr.size()) return true;  // torn FINAL: drop
      set_error("delta chain torn mid-chain at seq "
                + std::to_string(seq64) + " under " + root
                + " — restore the file or load an older full dump");
      return false;
    }
    for (const DeltaPayload& pl : payloads) {
      auto it = model->by_name.find(pl.name);
      if (it == model->by_name.end()) continue;   // unknown var: skip
      if (!apply_delta_payload(it->second, pl,
                               root + " seq "
                               + std::to_string(seq64))) {
        return false;
      }
    }
    for (auto& mf : maps) model->payloads.push_back(std::move(mf));
    model->version = seq64;
  }
  return true;
}

}  // namespace

extern "C" {

const char* oe_last_error(void) { return g_error.c_str(); }

oe_model* oe_model_load(const char* path) {
  g_error.clear();
  std::string meta_text;
  std::string root(path);
  if (!read_file(root + "/model_meta", &meta_text)) {
    set_error("cannot read " + root + "/model_meta");
    return nullptr;
  }
  JsonParser jp{meta_text.c_str(), meta_text.c_str() + meta_text.size()};
  Json meta = jp.parse();
  if (!jp.ok || meta.kind != Json::kObj) {
    set_error("model_meta is not valid JSON");
    return nullptr;
  }
  auto model = std::make_unique<oe_model>();
  if (const Json* s = meta.get("model_sign")) model->sign = s->str;
  const Json* vars = meta.get("variables");
  if (!vars || vars->kind != Json::kArr) {
    set_error("model_meta has no variables list");
    return nullptr;
  }
  // 2^63: the unbounded-vocab marker (reference Meta.h use_hash_table)
  const double kUnbounded = 9.0e18;
  for (const Json& v : vars->arr) {
    auto var = std::make_unique<oe_variable>();
    if (const Json* n = v.get("name")) var->name = n->str;
    if (const Json* i = v.get("variable_id")) {
      if (!json_int(i, &var->variable_id)) {
        set_error("corrupt variable_id for " + var->name);
        return nullptr;
      }
    }
    // ModelVariableMeta serializes flat: datatype/embedding_dim/
    // vocabulary_size alongside variable_id/name (meta.py to_json);
    // an out-of-int-range dim stays 0 and is refused just below
    if (const Json* d = v.get("embedding_dim")) json_int(d, &var->dim);
    double vocab = 0;
    if (const Json* vv = v.get("vocabulary_size")) vocab = vv->num;
    if (var->dim <= 0) {
      set_error("variable " + var->name + " has no embedding_dim");
      return nullptr;
    }
    bool hash = vocab >= kUnbounded;
    // the bounded-path cast below is UB for NaN/negative-huge vocab
    // (float-cast-overflow) — refuse anything not a plain row count
    if (!hash && !(vocab >= 0 && vocab <= 9.0e18)) {
      set_error("corrupt vocabulary_size for " + var->name);
      return nullptr;
    }
    var->vocab = hash ? -1 : static_cast<int64_t>(vocab);

    std::string safe = var->name;
    for (char& c : safe) {
      if (c == '/') c = '_';
    }
    size_t pos;
    while ((pos = safe.find(':')) != std::string::npos)
      safe.replace(pos, 1, "__");
    std::string vdir = root + "/var_" + std::to_string(var->variable_id)
        + "_" + safe + ".d";
    // single-host dumps: weights.npy (+ keys.npy for hash). Multi-host
    // dumps: part<k>_weights.npy with part<k>_{ids,keys}.npy — the
    // reference's per-node dump files.
    std::vector<std::string> prefixes;
    {
      struct stat st;
      if (::stat((vdir + "/weights.npy").c_str(), &st) == 0) {
        prefixes.push_back("");
      } else {
        for (int k = 0; k < (1 << 20); ++k) {
          std::string p = "part" + std::to_string(k) + "_";
          if (::stat((vdir + "/" + p + "weights.npy").c_str(), &st) != 0)
            break;
          prefixes.push_back(p);
        }
      }
    }
    if (prefixes.empty()) {
      set_error("no weights files under " + vdir);
      return nullptr;
    }
    var->direct = !hash && prefixes.size() == 1 && prefixes[0].empty();
    for (size_t k = 0; k < prefixes.size(); ++k) {
      auto w = open_npy(vdir + "/" + prefixes[k] + "weights.npy");
      if (!w) return nullptr;
      if (w->row_elems() != var->dim) {
        set_error("weights dim mismatch for " + var->name);
        return nullptr;
      }
      if (!weights_dtype_supported(*w)) {
        set_error("unsupported weights dtype " + w->dtype + " for "
                  + var->name);
        return nullptr;
      }
      var->total_rows += w->rows();
      std::string key_file = vdir + "/" + prefixes[k]
          + (hash ? "keys.npy" : "ids.npy");
      if (!var->direct) {
        auto kk = open_npy(key_file);
        if (!kk) return nullptr;
        if (!keys_dtype_supported(*kk)) {
          set_error("unsupported key dtype " + kk->dtype + " for "
                    + var->name);
          return nullptr;
        }
        if (kk->rows() != w->rows()) {
          set_error("key/row count mismatch for " + var->name);
          return nullptr;
        }
        int64_t n = kk->rows();
        var->index.reserve(var->index.size() + static_cast<size_t>(n) * 2);
        for (int64_t i = 0; i < n; ++i) {
          var->index[load_key_as_i64(*kk, i)] =
              (static_cast<int64_t>(k) << 40) | i;
        }
        var->keys.push_back(std::move(kk));
      }
      var->weights.push_back(std::move(w));
    }
    // a single dense part must hold exactly its vocabulary: a key
    // bound-checked against the meta vocab must never index past the rows
    if (var->direct && var->weights[0]->rows() != var->vocab) {
      set_error("weights rows " + std::to_string(var->weights[0]->rows())
                + " != vocabulary " + std::to_string(var->vocab)
                + " for " + var->name);
      return nullptr;
    }
    model->by_name[var->name] = var.get();
    model->by_id[var->variable_id] = var.get();
    model->variables.push_back(std::move(var));
  }
  // delta-compacted dirs load directly: crc-verified chain replay over
  // the mapped base (torn-final recovery matching load_checkpoint)
  if (!replay_delta_chain(model.get(), root)) return nullptr;
  return model.release();
}

void oe_model_free(oe_model* model) { delete model; }

const char* oe_model_sign(const oe_model* model) {
  return model->sign.c_str();
}

int oe_model_num_variables(const oe_model* model) {
  return static_cast<int>(model->variables.size());
}

oe_variable* oe_model_variable(oe_model* model, const char* name) {
  auto it = model->by_name.find(name);
  if (it == model->by_name.end()) {
    set_error(std::string("unknown variable ") + name);
    return nullptr;
  }
  return it->second;
}

oe_variable* oe_model_variable_by_id(oe_model* model, int variable_id) {
  auto it = model->by_id.find(variable_id);
  if (it == model->by_id.end()) {
    set_error("unknown variable id " + std::to_string(variable_id));
    return nullptr;
  }
  return it->second;
}

const char* oe_variable_name(const oe_variable* var) {
  return var->name.c_str();
}
int oe_variable_id(const oe_variable* var) { return var->variable_id; }
int oe_variable_dim(const oe_variable* var) { return var->dim; }
int64_t oe_variable_vocab(const oe_variable* var) { return var->vocab; }
int64_t oe_variable_rows(const oe_variable* var) {
  return var->total_rows;
}

int oe_pull_weights(const oe_variable* var, const int64_t* keys, int64_t n,
                    float* out) {
  g_error.clear();
  const int dim = var->dim;
  for (int64_t i = 0; i < n; ++i) {
    int64_t part = 0;
    int64_t row = resolve_row(var, keys[i], &part);
    copy_row(var, part, row, out + i * dim);
  }
  return 0;
}

int oe_pull_weights_gather(const oe_variable* var,
                           const int64_t* unique_keys, int64_t n_unique,
                           const int64_t* gather, int64_t n_out,
                           float* out) {
  // the micro-batcher's native data plane: every UNIQUE key probes the
  // index exactly once, then the scatter is pure row memcpy — a storm
  // of overlapping lookups pays one probe per distinct key per flush
  g_error.clear();
  const int dim = var->dim;
  std::vector<int64_t> parts(static_cast<size_t>(n_unique));
  std::vector<int64_t> rows(static_cast<size_t>(n_unique));
  for (int64_t u = 0; u < n_unique; ++u) {
    rows[u] = resolve_row(var, unique_keys[u], &parts[u]);
  }
  for (int64_t i = 0; i < n_out; ++i) {
    int64_t g = gather[i];
    if (g < 0 || g >= n_unique) {
      std::memset(out + i * dim, 0, sizeof(float) * dim);
      continue;
    }
    copy_row(var, parts[g], rows[g], out + i * dim);
  }
  return 0;
}

int64_t oe_model_version(const oe_model* model) { return model->version; }

}  // extern "C"
