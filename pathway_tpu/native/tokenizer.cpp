// Native batch tokenizer for the TPU data plane.
//
// TPU-native counterpart of the reference's native text handling (the
// reference tokenizes inside Rust connectors/parsers and relies on HF
// tokenizers for models). Feature-hashing tokenization: lowercase,
// alnum-run splitting, CRC32 token ids — identical semantics to
// models/tokenizer.py HashTokenizer, ~20x faster, writing each text's ids
// into its row of an int32 [batch, seq] buffer and its length beside it
// (models/tokenizer.py tokenize_batch lays them into the encoder's slabs).
//
// Built as a shared library at first use (see native/__init__.py); the
// Python implementation stays as the fallback.

#include <cstddef>
#include <cstdint>
#include <vector>

namespace {

constexpr int32_t PAD_ID = 0;
constexpr int32_t CLS_ID = 1;
constexpr int32_t SEP_ID = 2;
constexpr int32_t RESERVED = 4;

// standard CRC-32 (IEEE 802.3), bit-reflected, table-driven — matches
// python's zlib.crc32
struct Crc32Table {
    uint32_t table[256];
    Crc32Table() {
        for (uint32_t i = 0; i < 256; i++) {
            uint32_t c = i;
            for (int k = 0; k < 8; k++) {
                c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
            }
            table[i] = c;
        }
    }
};

const Crc32Table kCrc;

// crc32 of the token with A-Z lowered, a byte at a time: a token of any
// length hashes as python's text.lower() would have it
inline uint32_t crc32_lowered(const unsigned char* buf, size_t len) {
    uint32_t crc = 0xFFFFFFFFu;
    for (size_t i = 0; i < len; i++) {
        unsigned char ch = buf[i];
        if (ch >= 'A' && ch <= 'Z') ch += 32;
        crc = kCrc.table[(crc ^ ch) & 0xFF] ^ (crc >> 8);
    }
    return crc ^ 0xFFFFFFFFu;
}

inline bool is_alnum_ascii(unsigned char c) {
    return (c >= '0' && c <= '9') || (c >= 'a' && c <= 'z') ||
           (c >= 'A' && c <= 'Z');
}

// the ASCII characters python's `\s` takes: isspace()'s six and the four
// separators 0x1C-0x1F (str.isspace() is true of them)
inline bool is_space_ascii(unsigned char c) {
    return c == ' ' || (c >= 0x09 && c <= 0x0D) || (c >= 0x1C && c <= 0x1F);
}

}  // namespace

extern "C" {

// Tokenize one text into out_ids[0..max_len); returns number of ids
// written (including CLS/SEP). Splitting: runs of ASCII alnum are words;
// any other non-space byte is a single-char token (UTF-8 multibyte
// sequences group into one token), mirroring HashTokenizer's regex
// `[A-Za-z0-9]+|[^\sA-Za-z0-9]`.
int32_t tokenize_one(const char* text, int32_t text_len, int32_t vocab_size,
                     int32_t max_len, int32_t* out_ids) {
    int32_t n = 0;
    if (max_len <= 0) return 0;
    out_ids[n++] = CLS_ID;
    const unsigned char* s = reinterpret_cast<const unsigned char*>(text);
    int32_t i = 0;
    while (i < text_len && n < max_len) {
        unsigned char c = s[i];
        if (is_space_ascii(c)) {
            i++;
            continue;
        }
        int32_t start = i;
        if (is_alnum_ascii(c)) {
            while (i < text_len && is_alnum_ascii(s[i])) i++;
        } else if (c < 0x80) {
            i++;  // single ascii punct char
        } else {
            // one UTF-8 multibyte sequence = one token
            i++;
            while (i < text_len && (s[i] & 0xC0) == 0x80) i++;
        }
        uint32_t h = crc32_lowered(s + start, (size_t)(i - start));
        out_ids[n++] = RESERVED + (int32_t)(h % (uint32_t)(vocab_size - RESERVED));
    }
    if (n < max_len) {
        out_ids[n++] = SEP_ID;
    }
    // on truncation SEP is dropped, matching HashTokenizer.encode's
    // ids[:max_len] semantics
    return n;
}

// Batch API: texts as one concatenated buffer with offsets; text t goes to
// row rows[t] of ids[*, seq_len] and its number of ids to lengths[rows[t]]
// (the caller's other rows are left alone: it tokenises those itself).
// Re-entrant: no state but the arguments.
void tokenize_batch(const char* buffer, const int64_t* offsets,
                    const int64_t* rows, int32_t n_texts, int32_t vocab_size,
                    int32_t seq_len, int32_t* ids, int32_t* lengths) {
    for (int32_t t = 0; t < n_texts; t++) {
        const char* text = buffer + offsets[t];
        int32_t text_len = (int32_t)(offsets[t + 1] - offsets[t]);
        int64_t r = rows[t];
        lengths[r] = tokenize_one(text, text_len, vocab_size, seq_len,
                                  ids + r * seq_len);
    }
}

// Greedy first fit of `pack_batch`: documents in the order `order` go each
// to the first row with room for `lengths[d]` more tokens of its `slab` and
// fewer than `max_segments` documents, or open a new row.  Writes document
// d's row, its segment there and its first slot at index d.
void first_fit(const int32_t* lengths, const int64_t* order, int32_t n,
               int32_t slab, int32_t max_segments, int32_t* row_of,
               int32_t* seg_of, int32_t* at_of) {
    std::vector<int32_t> used, held;  // tokens and documents of each row
    for (int32_t k = 0; k < n; k++) {
        int64_t d = order[k];
        int32_t need = lengths[d];
        size_t row = 0;
        while (row < used.size() &&
               !(used[row] + need <= slab && held[row] < max_segments)) {
            row++;
        }
        if (row == used.size()) {
            used.push_back(0);
            held.push_back(0);
        }
        row_of[d] = (int32_t)row;
        seg_of[d] = held[row]++;
        at_of[d] = used[row];
        used[row] += need;
    }
}

// Token counting (splitters use it): number of word tokens, no specials.
int32_t count_tokens(const char* text, int32_t text_len) {
    const unsigned char* s = reinterpret_cast<const unsigned char*>(text);
    int32_t i = 0, count = 0;
    while (i < text_len) {
        unsigned char c = s[i];
        if (is_space_ascii(c)) {
            i++;
            continue;
        }
        if (is_alnum_ascii(c)) {
            while (i < text_len && is_alnum_ascii(s[i])) i++;
        } else if (c < 0x80) {
            i++;
        } else {
            i++;
            while (i < text_len && (s[i] & 0xC0) == 0x80) i++;
        }
        count++;
    }
    return count;
}

}  // extern "C"
