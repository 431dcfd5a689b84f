// Native parquet column-chunk scanner — the host-side data-loader hot loop.
//
// Reference analog: the reference's parquet host path (GpuParquetScan.scala
// readPartFile:818) copies row-group bytes and hands them to libcudf (C++)
// for decode; its native layer owns all byte-level work. Here the device
// (XLA/Pallas) unpacks the bulk bit-packed indices, and THIS translation
// unit owns the byte-level host work that remained in Python: thrift
// compact-protocol page headers, definition-level RLE decode, and RLE/
// bit-packed hybrid run segmentation. One C call per column chunk replaces
// the per-page/per-varint Python loops (io/parquet_native.py keeps the same
// logic as documentation and fallback).
//
// Layout contract with spark_rapids_tpu/native/__init__.py (ctypes):
// every struct field is int64_t, arrays are caller-allocated.

#include <cstdint>
#include <cstring>

namespace {

struct Reader {
    const uint8_t* buf;
    int64_t len;
    int64_t pos;
    bool fail = false;

    uint8_t byte() {
        if (pos >= len) { fail = true; return 0; }
        return buf[pos++];
    }
    uint64_t varint() {
        uint64_t out = 0;
        int shift = 0;
        while (true) {
            uint8_t b = byte();
            if (fail || shift > 63) { fail = true; return 0; }
            out |= static_cast<uint64_t>(b & 0x7F) << shift;
            if (!(b & 0x80)) return out;
            shift += 7;
        }
    }
    int64_t zigzag() {
        uint64_t v = varint();
        return static_cast<int64_t>(v >> 1) ^ -static_cast<int64_t>(v & 1);
    }
    void skip(int64_t n) {
        if (n < 0 || pos + n > len) { fail = true; return; }
        pos += n;
    }
    void skip_binary() { skip(static_cast<int64_t>(varint())); }
};

// Minimal thrift compact struct walk keeping only the page-header fields we
// need (same field ids as io/parquet_native.py parse_page_header).
struct PageHeaderFields {
    int64_t page_type = -1;         // field 1
    int64_t uncompressed_size = 0;  // field 2
    int64_t compressed_size = 0;    // field 3
    int64_t num_values = 0;         // nested field 1
    int64_t encoding = 0;           // nested field 2 (v1/dict) or 4 (v2)
};

void walk_struct(Reader& r, int depth, int64_t parent_field,
                 PageHeaderFields& out) {
    int64_t fid = 0;
    while (!r.fail) {
        uint8_t head = r.byte();
        if (r.fail || head == 0) return;
        int64_t delta = head >> 4;
        int ftype = head & 0x0F;
        fid = delta ? fid + delta : r.zigzag();
        int64_t val = 0;
        switch (ftype) {
            case 1: val = 1; break;            // BOOLEAN_TRUE
            case 2: val = 0; break;            // BOOLEAN_FALSE
            case 3: val = r.byte(); break;     // byte
            case 4: case 5: case 6:            // i16/i32/i64
                val = r.zigzag(); break;
            case 7: r.skip(8); break;          // double
            case 8: r.skip_binary(); break;    // binary/string
            case 12:                            // struct
                walk_struct(r, depth + 1, fid, out);
                break;
            case 9: case 10: {                  // list/set
                uint8_t sz = r.byte();
                int64_t n = sz >> 4;
                int et = sz & 0x0F;
                if (n == 15) n = static_cast<int64_t>(r.varint());
                for (int64_t i = 0; i < n && !r.fail; i++) {
                    if (et == 4 || et == 5 || et == 6) r.zigzag();
                    else if (et == 8) r.skip_binary();
                    else if (et == 12) walk_struct(r, depth + 1, -1, out);
                    else if (et == 3) r.byte();
                    else if (et == 7) r.skip(8);
                    else { r.fail = true; }
                }
                break;
            }
            default:
                r.fail = true;
                return;
        }
        if (depth == 0) {
            if (fid == 1) out.page_type = val;
            else if (fid == 2) out.uncompressed_size = val;
            else if (fid == 3) out.compressed_size = val;
        } else if (depth == 1 &&
                   (parent_field == 5 || parent_field == 7 ||
                    parent_field == 8)) {
            // DataPageHeader(5) / DictionaryPageHeader(7) / DataPageHeaderV2(8)
            if (fid == 1) out.num_values = val;
            if ((parent_field == 8 && fid == 4) ||
                (parent_field != 8 && fid == 2))
                out.encoding = val;
        }
    }
}

}  // namespace

extern "C" {

struct SrSeg {
    int64_t kind;       // 0 = rle, 1 = packed
    int64_t count;
    int64_t value;
    int64_t byte_off;   // page-body-relative
    int64_t byte_len;
};

struct SrPage {
    int64_t num_values;
    int64_t def_off;     // start of this page's levels in def_levels out
    int64_t n_present;
    int64_t bit_width;
    int64_t body_off;    // page body offset in buf
    int64_t body_len;
    int64_t values_off;  // page-relative offset of the bit-width byte
    int64_t seg_off;
    int64_t seg_count;
};

// error codes (mirror the Python parser's NotImplementedError scope)
enum {
    SR_ERR_MALFORMED = -1,
    SR_ERR_PAGE_TYPE = -2,
    SR_ERR_ENCODING = -3,
    SR_ERR_CAPACITY = -4,      // pages/segs arrays too small: caller may grow
    SR_ERR_NO_DICT = -5,
    SR_ERR_DEF_CAPACITY = -6,  // def levels exceed footer num_values: corrupt
};

// Decode an RLE/bit-packed hybrid region. When `levels_out` is non-null the
// values are materialized (definition levels); otherwise only the run
// STRUCTURE is recorded into segs (bit-packed payload goes to the device).
static int64_t scan_hybrid(const uint8_t* page, int64_t page_len, int64_t pos,
                           int64_t end, int64_t bit_width, int64_t total,
                           SrSeg* segs, int64_t segs_cap, int64_t* n_segs,
                           int32_t* levels_out) {
    Reader r{page, end < page_len ? end : page_len, pos};
    int64_t got = 0;
    int64_t vbytes = (bit_width + 7) / 8;
    while (got < total && r.pos < r.len && !r.fail) {
        uint64_t h = r.varint();
        if (r.fail) return SR_ERR_MALFORMED;
        SrSeg s{};
        if (h & 1) {
            int64_t groups = static_cast<int64_t>(h >> 1);
            int64_t n = groups * 8;
            s.kind = 1;
            s.count = n < total - got ? n : total - got;
            s.byte_off = r.pos;
            s.byte_len = groups * bit_width;
            if (levels_out) {
                // unpack little-endian bit order
                for (int64_t i = 0; i < s.count; i++) {
                    int64_t bit0 = i * bit_width;
                    int64_t v = 0;
                    for (int64_t b = 0; b < bit_width; b++) {
                        int64_t bit = bit0 + b;
                        int64_t byi = r.pos + (bit >> 3);
                        if (byi >= r.len) return SR_ERR_MALFORMED;
                        v |= ((page[byi] >> (bit & 7)) & 1) << b;
                    }
                    levels_out[got + i] = static_cast<int32_t>(v);
                }
            }
            r.skip(s.byte_len);
            if (r.fail) return SR_ERR_MALFORMED;
        } else {
            int64_t run = static_cast<int64_t>(h >> 1);
            int64_t v = 0;
            for (int64_t i = 0; i < vbytes; i++)
                v |= static_cast<int64_t>(r.byte()) << (8 * i);
            if (r.fail) return SR_ERR_MALFORMED;
            s.kind = 0;
            s.count = run < total - got ? run : total - got;
            s.value = v;
            if (levels_out)
                for (int64_t i = 0; i < s.count; i++)
                    levels_out[got + i] = static_cast<int32_t>(v);
        }
        if (segs) {
            if (*n_segs >= segs_cap) return SR_ERR_CAPACITY;
            segs[(*n_segs)++] = s;
        }
        got += s.count;
    }
    return got;
}

// The run structure of one hybrid index stream, page[pos, end): for a page
// body that was decompressed on the host. Returns the segment count or a
// negative SR_ERR_* code.
int64_t sr_scan_hybrid(const uint8_t* page, int64_t page_len, int64_t pos,
                       int64_t end, int64_t bit_width, int64_t total,
                       SrSeg* segs, int64_t segs_cap) {
    int64_t n_segs = 0;
    int64_t got = scan_hybrid(page, page_len, pos, end, bit_width, total,
                              segs, segs_cap, &n_segs, nullptr);
    return got < 0 ? got : n_segs;
}

// Scan one UNCOMPRESSED dictionary-encoded column chunk buffer.
// Returns the page count (>= 0) or a negative SR_ERR_* code.
// dict_out = {body_off, body_len, num_values}.
int64_t sr_scan_chunk(const uint8_t* buf, int64_t buf_len,
                      int64_t col_num_values, int32_t max_def,
                      SrPage* pages, int64_t pages_cap,
                      SrSeg* segs, int64_t segs_cap,
                      int32_t* def_levels, int64_t def_cap,
                      int64_t* dict_out) {
    int64_t pos = 0, n_pages = 0, n_segs = 0;
    int64_t values_seen = 0, def_used = 0;
    dict_out[0] = dict_out[1] = dict_out[2] = -1;
    while (pos < buf_len && values_seen < col_num_values) {
        Reader r{buf, buf_len, pos};
        PageHeaderFields ph;
        walk_struct(r, 0, -1, ph);
        if (r.fail) return SR_ERR_MALFORMED;
        int64_t header_len = r.pos - pos;
        int64_t body = pos + header_len;
        if (body + ph.compressed_size > buf_len) return SR_ERR_MALFORMED;
        if (ph.page_type == 2) {                      // dictionary page
            dict_out[0] = body;
            dict_out[1] = ph.compressed_size;
            dict_out[2] = ph.num_values;
        } else if (ph.page_type == 0) {               // data page v1
            if (ph.encoding != 8 && ph.encoding != 2)
                return SR_ERR_ENCODING;               // RLE_DICT / PLAIN_DICT
            if (n_pages >= pages_cap) return SR_ERR_CAPACITY;
            const uint8_t* page = buf + body;
            int64_t page_len = ph.compressed_size;
            int64_t p = 0;
            SrPage out{};
            out.num_values = ph.num_values;
            out.body_off = body;
            out.body_len = page_len;
            out.def_off = def_used;
            // def_cap is exactly the footer's num_values: overflow means a
            // corrupt chunk, not an undersized caller array — growing the
            // other buffers can never fix it
            if (def_used + ph.num_values > def_cap) return SR_ERR_DEF_CAPACITY;
            if (max_def) {
                if (p + 4 > page_len) return SR_ERR_MALFORMED;
                int64_t dl_len = 0;
                std::memcpy(&dl_len, page + p, 4);
                p += 4;
                int64_t got = scan_hybrid(page, page_len, p, p + dl_len, 1,
                                          ph.num_values, nullptr, 0, &n_segs,
                                          def_levels + def_used);
                if (got < 0) return got;
                for (int64_t i = got; i < ph.num_values; i++)
                    def_levels[def_used + i] = 0;
                p += dl_len;
            } else {
                for (int64_t i = 0; i < ph.num_values; i++)
                    def_levels[def_used + i] = 1;
            }
            int64_t n_present = 0;
            for (int64_t i = 0; i < ph.num_values; i++)
                n_present += def_levels[def_used + i];
            def_used += ph.num_values;
            if (p >= page_len) return SR_ERR_MALFORMED;
            out.bit_width = page[p];
            out.values_off = p;
            p += 1;
            out.n_present = n_present;
            out.seg_off = n_segs;
            int64_t got = scan_hybrid(page, page_len, p, page_len,
                                      out.bit_width, n_present, segs,
                                      segs_cap, &n_segs, nullptr);
            if (got < 0) return got;
            out.seg_count = n_segs - out.seg_off;
            pages[n_pages++] = out;
            values_seen += ph.num_values;
        } else {
            return SR_ERR_PAGE_TYPE;                  // v2 etc: fallback
        }
        pos = body + ph.compressed_size;
    }
    if (dict_out[0] < 0) return SR_ERR_NO_DICT;
    return n_pages;
}

}  // extern "C"
