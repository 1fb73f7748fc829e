// EDLIO container codec — C++ core with a C ABI for ctypes bindings.
//
// Implements FORMAT.md exactly (interchangeable with _pyimpl.py).  This is
// the TPU build's replacement for the reference's native record dependency
// (Go `pyrecordio`, used via elasticdl/python/data/reader/recordio_reader.py):
// a seekable record container with O(1) num_records and ranged scans, which
// is what task-addressable dynamic data sharding requires.
//
// Build: python -m elasticdl_tpu.data.recordio.build
//
// Design notes:
// - Scanner exposes a *batch* read (fill a caller buffer with as many
//   concatenated payloads as fit) so the Python side pays one FFI call per
//   few thousand records, not per record.
// - Buffered IO with a 1 MiB read-ahead; CRC32 via zlib.

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include <zlib.h>

namespace {

constexpr uint32_t kMagic = 0x45444C49;  // "EDLI"
constexpr uint32_t kVersion = 1;
constexpr size_t kFooterSize = 8 + 8 + 4 + 4;
constexpr size_t kFrameSize = 4 + 4;

thread_local std::string g_last_error;

void set_error(const std::string& msg) { g_last_error = msg; }

uint32_t load_u32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

uint64_t load_u64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;
}

struct Footer {
  uint64_t index_offset;
  uint64_t num_records;
};

bool read_footer(std::FILE* f, Footer* out) {
  if (std::fseek(f, 0, SEEK_END) != 0) {
    set_error("seek to end failed");
    return false;
  }
  long size = std::ftell(f);
  if (size < (long)kFooterSize) {
    set_error("file smaller than footer");
    return false;
  }
  uint8_t buf[kFooterSize];
  if (std::fseek(f, size - (long)kFooterSize, SEEK_SET) != 0 ||
      std::fread(buf, 1, kFooterSize, f) != kFooterSize) {
    set_error("footer read failed");
    return false;
  }
  uint32_t version = load_u32(buf + 16);
  uint32_t magic = load_u32(buf + 20);
  if (magic != kMagic) {
    set_error("bad magic (not an EDLIO file or truncated)");
    return false;
  }
  if (version != kVersion) {
    set_error("unsupported EDLIO version");
    return false;
  }
  out->index_offset = load_u64(buf);
  out->num_records = load_u64(buf + 8);
  return true;
}

struct Writer {
  std::FILE* f;
  std::vector<uint64_t> offsets;
  uint64_t pos = 0;
};

struct Scanner {
  std::FILE* f;
  int64_t remaining = 0;
};

}  // namespace

extern "C" {

const char* edlio_last_error() { return g_last_error.c_str(); }

// The digest of the source this library was built from (build.py passes
// it; the loader compares it with today's _native.cc, so a library built
// from other source is rebuilt instead of loaded).
#ifndef EDLIO_SOURCE_DIGEST
#define EDLIO_SOURCE_DIGEST "EDLIO_SOURCE_SHA256=unknown"
#endif
const char* edlio_source_digest() { return EDLIO_SOURCE_DIGEST; }

void* edlio_writer_open(const char* path) {
  std::FILE* f = std::fopen(path, "wb");
  if (!f) {
    set_error(std::string("cannot open for write: ") + path);
    return nullptr;
  }
  auto* w = new Writer();
  w->f = f;
  return w;
}

int edlio_writer_write(void* handle, const uint8_t* data, uint64_t len) {
  auto* w = static_cast<Writer*>(handle);
  uint32_t len32 = (uint32_t)len;
  uint32_t crc = (uint32_t)crc32(0L, data, (uInt)len);
  w->offsets.push_back(w->pos);
  uint8_t frame[kFrameSize];
  std::memcpy(frame, &len32, 4);
  std::memcpy(frame + 4, &crc, 4);
  if (std::fwrite(frame, 1, kFrameSize, w->f) != kFrameSize ||
      std::fwrite(data, 1, len, w->f) != len) {
    set_error("write failed");
    return -1;
  }
  w->pos += kFrameSize + len;
  return 0;
}

int edlio_writer_close(void* handle) {
  auto* w = static_cast<Writer*>(handle);
  int rc = 0;
  uint64_t index_offset = w->pos;
  for (uint64_t off : w->offsets) {
    if (std::fwrite(&off, 1, 8, w->f) != 8) rc = -1;
  }
  uint64_t n = w->offsets.size();
  uint8_t footer[kFooterSize];
  std::memcpy(footer, &index_offset, 8);
  std::memcpy(footer + 8, &n, 8);
  std::memcpy(footer + 16, &kVersion, 4);
  std::memcpy(footer + 20, &kMagic, 4);
  if (std::fwrite(footer, 1, kFooterSize, w->f) != kFooterSize) rc = -1;
  if (std::fclose(w->f) != 0) rc = -1;
  if (rc != 0) set_error("writer close/flush failed");
  delete w;
  return rc;
}

int64_t edlio_num_records(const char* path) {
  std::FILE* f = std::fopen(path, "rb");
  if (!f) {
    set_error(std::string("cannot open: ") + path);
    return -1;
  }
  Footer footer;
  bool ok = read_footer(f, &footer);
  std::fclose(f);
  return ok ? (int64_t)footer.num_records : -1;
}

void* edlio_scanner_open(const char* path, int64_t start, int64_t length) {
  std::FILE* f = std::fopen(path, "rb");
  if (!f) {
    set_error(std::string("cannot open: ") + path);
    return nullptr;
  }
  Footer footer;
  if (!read_footer(f, &footer)) {
    std::fclose(f);
    return nullptr;
  }
  if (start < 0 || (uint64_t)start > footer.num_records) {
    set_error("start out of range");
    std::fclose(f);
    return nullptr;
  }
  int64_t avail = (int64_t)footer.num_records - start;
  int64_t remaining = length < 0 ? avail : (length < avail ? length : avail);
  if (remaining > 0) {
    uint8_t off_buf[8];
    if (std::fseek(f, (long)(footer.index_offset + 8 * (uint64_t)start),
                   SEEK_SET) != 0 ||
        std::fread(off_buf, 1, 8, f) != 8) {
      set_error("index read failed");
      std::fclose(f);
      return nullptr;
    }
    uint64_t first = load_u64(off_buf);
    if (std::fseek(f, (long)first, SEEK_SET) != 0) {
      set_error("seek to first record failed");
      std::fclose(f);
      return nullptr;
    }
  }
  // large stdio buffer => read-ahead without mmap portability questions
  std::setvbuf(f, nullptr, _IOFBF, 1 << 20);
  auto* s = new Scanner();
  s->f = f;
  s->remaining = remaining;
  return s;
}

// Fill `buf` (capacity `buf_cap`) with concatenated payloads; write each
// payload's length into `lengths` (capacity `max_records`).  Returns the
// number of records read; 0 at end of range; -1 on error.  A record larger
// than buf_cap is an error (caller sizes the buffer generously).
int64_t edlio_scanner_next_batch(void* handle, uint8_t* buf, uint64_t buf_cap,
                                 uint64_t* lengths, int64_t max_records) {
  auto* s = static_cast<Scanner*>(handle);
  int64_t count = 0;
  uint64_t used = 0;
  while (count < max_records && s->remaining > 0) {
    uint8_t frame[kFrameSize];
    long before = std::ftell(s->f);
    if (std::fread(frame, 1, kFrameSize, s->f) != kFrameSize) {
      set_error("truncated frame header");
      return -1;
    }
    uint32_t len = load_u32(frame);
    uint32_t crc = load_u32(frame + 4);
    if (used + len > buf_cap) {
      if (count == 0) {
        set_error("record larger than batch buffer");
        return -1;
      }
      // rewind to frame start; deliver what we have
      std::fseek(s->f, before, SEEK_SET);
      break;
    }
    if (std::fread(buf + used, 1, len, s->f) != len) {
      set_error("truncated payload");
      return -1;
    }
    if ((uint32_t)crc32(0L, buf + used, (uInt)len) != crc) {
      set_error("crc mismatch");
      return -1;
    }
    lengths[count] = len;
    used += len;
    ++count;
    --s->remaining;
  }
  return count;
}

void edlio_scanner_close(void* handle) {
  auto* s = static_cast<Scanner*>(handle);
  std::fclose(s->f);
  delete s;
}

}  // extern "C"

// ---- fused batch decode of example payloads --------------------------------
//
// The vectorized half of the data loader (the role tf.data's C++ runtime
// plays for the reference, SURVEY §2.9): decode N example payloads — each a
// tensor-frame collection produced by utils/tensor.py serialize_tensors —
// straight into caller-allocated (N, ...) batch arrays, one memcpy per
// (record, feature), no per-record Python objects.
//
// Payload layout (utils/tensor.py): [u32 nframes] ([u32 flen] frame)*
//   frame = [u32 hdr_len] header_json data [indices?]
//   header_json (canonical json.dumps order, space separators):
//     {"name": "...", "dtype": "...", "shape": [a, b], "sparse": false}
//
// The parser accepts exactly the canonical layout; anything else (sparse
// tensors, escaped names, re-ordered keys from a foreign writer) returns a
// negative code and the Python caller falls back to the per-record path —
// correctness never depends on this fast path.

namespace {

struct HdrCursor {
  const uint8_t* p;
  const uint8_t* end;
};

bool expect(HdrCursor* c, const char* lit) {
  size_t n = std::strlen(lit);
  if ((size_t)(c->end - c->p) < n || std::memcmp(c->p, lit, n) != 0) {
    return false;
  }
  c->p += n;
  return true;
}

// Parse a JSON string value with no escapes; returns false on escape/EOF.
bool parse_plain_string(HdrCursor* c, const uint8_t** out, size_t* out_len) {
  const uint8_t* start = c->p;
  while (c->p < c->end && *c->p != '"') {
    if (*c->p == '\\') return false;
    ++c->p;
  }
  if (c->p >= c->end) return false;
  *out = start;
  *out_len = (size_t)(c->p - start);
  ++c->p;  // closing quote
  return true;
}

bool parse_int(HdrCursor* c, int64_t* out) {
  if (c->p >= c->end || *c->p < '0' || *c->p > '9') return false;
  int64_t v = 0;
  int digits = 0;
  while (c->p < c->end && *c->p >= '0' && *c->p <= '9') {
    if (++digits > 18) return false;  // corrupt header: would overflow i64
    v = v * 10 + (*c->p - '0');
    ++c->p;
  }
  *out = v;
  return true;
}

}  // namespace

extern "C" {

// Decode n_records payloads (concatenated in buf, record i spanning
// [offsets[i], offsets[i+1])) into n_features batch arrays.  Feature k of
// record i lands at outs[k] + i * row_bytes[k].  Every record must carry
// exactly the expected features (any order), each matching the expected
// dtype / shape / byte count.  Returns 0 on success, negative on any
// mismatch (caller falls back to the per-record Python decoder).
int64_t edl_decode_batch(const uint8_t* buf, const uint64_t* offsets,
                         int64_t n_records, int32_t n_features,
                         const char** names, const char** dtypes,
                         const int64_t* shapes, const int32_t* ndims,
                         const uint64_t* row_bytes, uint8_t** outs) {
  if (n_features <= 0 || n_features > 64) return -1;  // seen-mask is u64
  // per-feature offset into the flattened expected-shape array
  std::vector<int32_t> shape_off(n_features);
  int32_t off = 0;
  for (int32_t k = 0; k < n_features; ++k) {
    shape_off[k] = off;
    off += ndims[k];
  }
  for (int64_t i = 0; i < n_records; ++i) {
    const uint8_t* p = buf + offsets[i];
    const uint8_t* rec_end = buf + offsets[i + 1];
    if (rec_end - p < 4) return -2;
    uint32_t nframes = load_u32(p);
    p += 4;
    if ((int64_t)nframes != n_features) return -3;
    uint64_t seen = 0;
    for (uint32_t f = 0; f < nframes; ++f) {
      if (rec_end - p < 8) return -4;
      uint32_t flen = load_u32(p);
      uint32_t hdr_len = load_u32(p + 4);
      p += 8;
      if ((uint64_t)(rec_end - p) + 4 < (uint64_t)flen ||
          (uint64_t)hdr_len + 4 > (uint64_t)flen) {
        return -5;
      }
      const uint8_t* frame_end = p + (flen - 4);
      HdrCursor c{p, p + hdr_len};
      p += hdr_len;
      // canonical header walk
      const uint8_t* name;
      size_t name_len;
      const uint8_t* dtype;
      size_t dtype_len;
      if (!expect(&c, "{\"name\": \"") ||
          !parse_plain_string(&c, &name, &name_len) ||
          !expect(&c, ", \"dtype\": \"") ||
          !parse_plain_string(&c, &dtype, &dtype_len) ||
          !expect(&c, ", \"shape\": [")) {
        return -6;
      }
      // match the feature by name
      int32_t k = -1;
      for (int32_t j = 0; j < n_features; ++j) {
        if (std::strlen(names[j]) == name_len &&
            std::memcmp(names[j], name, name_len) == 0) {
          k = j;
          break;
        }
      }
      if (k < 0 || (seen >> k) & 1) return -7;
      if (std::strlen(dtypes[k]) != dtype_len ||
          std::memcmp(dtypes[k], dtype, dtype_len) != 0) {
        return -8;
      }
      // shape must equal the expected per-record shape exactly
      for (int32_t d = 0; d < ndims[k]; ++d) {
        if (d > 0 && !expect(&c, ", ")) return -9;
        int64_t v;
        if (!parse_int(&c, &v) || v != shapes[shape_off[k] + d]) return -9;
      }
      if (!expect(&c, "]") || !expect(&c, ", \"sparse\": false}")) {
        return -10;  // sparse or trailing keys: not batchable here
      }
      if ((uint64_t)(frame_end - p) != row_bytes[k]) return -11;
      std::memcpy(outs[k] + (uint64_t)i * row_bytes[k], p, row_bytes[k]);
      p = frame_end;
      seen |= (uint64_t)1 << k;
    }
  }
  return 0;
}

}  // extern "C"
