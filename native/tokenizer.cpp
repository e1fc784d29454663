// Native .c2v tokenizer: the host input pipeline's hot loop.
//
// Replaces the Python per-token dict lookups in
// code2vec_tpu/data/reader.py::tokenize_rows with a multithreaded C++
// implementation (the reference leaned on tf.data's C++ CsvDataset for the
// same reason, path_context_reader.py:122-125). Semantics are identical:
//
//   line   := label ' ' ctx (' ' ctx)*            (trailing spaces = padding)
//   ctx    := source ',' path ',' target           (missing parts -> PAD,
//                                                   further parts dropped)
//   lookup := vocab.get(word, OOV); empty -> PAD
//   mask   := any of the three indices != its PAD index
//
// Exposed as a C API for ctypes (no pybind11 in this image).
#include <cstdint>
#include <deque>
#include <cstring>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

struct Vocab {
  // keys are views into `storage` (one retained copy of the words blob):
  // lookups take a string_view with NO per-token heap allocation
  std::unordered_map<std::string_view, int32_t> word_to_index;
  std::deque<std::string> storage;  // deque: elements never move
  int32_t oov = 0;
  int32_t pad = 0;

  // context parts: the reference's CSV default substitutes the PAD word
  // for empty fields before the hashtable lookup
  int32_t lookup(std::string_view word) const {
    if (word.empty()) return pad;
    auto it = word_to_index.find(word);
    return it == word_to_index.end() ? oov : it->second;
  }

  // labels: the reference's CSV default for the label column is the OOV
  // word (path_context_reader.py:82), so an empty label is OOV, not PAD
  int32_t lookup_label(std::string_view word) const {
    if (word.empty()) return oov;
    auto it = word_to_index.find(word);
    return it == word_to_index.end() ? oov : it->second;
  }
};

struct Tokenizer {
  Vocab token;
  Vocab path;
  Vocab target;
};

// Tokenize rows [row_begin, row_end) of the line buffer.
void tokenize_range(const Tokenizer* tok, const char* buf,
                    const int64_t* offsets, int32_t row_begin,
                    int32_t row_end, int32_t max_contexts, int32_t* src,
                    int32_t* path, int32_t* tgt, float* mask,
                    int32_t* label) {
  const int32_t token_pad = tok->token.pad;
  const int32_t path_pad = tok->path.pad;
  for (int32_t r = row_begin; r < row_end; ++r) {
    std::string_view line(buf + offsets[r],
                          static_cast<size_t>(offsets[r + 1] - offsets[r]));
    while (!line.empty() && (line.back() == '\n' || line.back() == '\r'))
      line.remove_suffix(1);

    int32_t* row_src = src + static_cast<int64_t>(r) * max_contexts;
    int32_t* row_path = path + static_cast<int64_t>(r) * max_contexts;
    int32_t* row_tgt = tgt + static_cast<int64_t>(r) * max_contexts;
    float* row_mask = mask + static_cast<int64_t>(r) * max_contexts;

    size_t pos = line.find(' ');
    std::string_view label_sv =
        pos == std::string_view::npos ? line : line.substr(0, pos);
    label[r] = tok->target.lookup_label(label_sv);

    int32_t c = 0;
    size_t start = pos == std::string_view::npos ? line.size() : pos + 1;
    while (c < max_contexts) {
      if (start > line.size()) break;
      size_t end = line.find(' ', start);
      if (end == std::string_view::npos) end = line.size();
      std::string_view ctx = line.substr(start, end - start);
      int32_t s_idx = token_pad, p_idx = path_pad, t_idx = token_pad;
      if (!ctx.empty()) {
        size_t c1 = ctx.find(',');
        if (c1 == std::string_view::npos) {
          s_idx = tok->token.lookup(ctx);
        } else {
          s_idx = tok->token.lookup(ctx.substr(0, c1));
          size_t c2 = ctx.find(',', c1 + 1);
          if (c2 == std::string_view::npos) {
            p_idx = tok->path.lookup(ctx.substr(c1 + 1));
          } else {
            p_idx = tok->path.lookup(ctx.substr(c1 + 1, c2 - c1 - 1));
            // a fourth part and beyond are dropped, as the Python
            // path's split(',') drops them
            t_idx = tok->token.lookup(
                ctx.substr(c2 + 1, ctx.find(',', c2 + 1) - c2 - 1));
          }
        }
      }
      row_src[c] = s_idx;
      row_path[c] = p_idx;
      row_tgt[c] = t_idx;
      row_mask[c] =
          (s_idx != token_pad || p_idx != path_pad || t_idx != token_pad)
              ? 1.0f
              : 0.0f;
      ++c;
      start = end + 1;
    }
    for (; c < max_contexts; ++c) {
      row_src[c] = token_pad;
      row_path[c] = path_pad;
      row_tgt[c] = token_pad;
      row_mask[c] = 0.0f;
    }
  }
}

Vocab* vocab_by_id(Tokenizer* tok, int32_t vocab_id) {
  switch (vocab_id) {
    case 0:
      return &tok->token;
    case 1:
      return &tok->path;
    case 2:
      return &tok->target;
  }
  return nullptr;
}

}  // namespace

extern "C" {

void* c2v_tok_create() { return new Tokenizer(); }

void c2v_tok_destroy(void* handle) {
  delete static_cast<Tokenizer*>(handle);
}

// words: '\n'-separated word list; indices: per-word vocab index.
void c2v_tok_add_words(void* handle, int32_t vocab_id, const char* words,
                       int64_t words_len, const int32_t* indices,
                       int32_t n_words) {
  Vocab* vocab = vocab_by_id(static_cast<Tokenizer*>(handle), vocab_id);
  if (!vocab) return;
  vocab->word_to_index.reserve(static_cast<size_t>(n_words) * 2);
  // retain one copy of the blob; map keys are views into it
  vocab->storage.emplace_back(words, static_cast<size_t>(words_len));
  std::string_view buf(vocab->storage.back());
  size_t start = 0;
  for (int32_t i = 0; i < n_words; ++i) {
    size_t end = buf.find('\n', start);
    if (end == std::string_view::npos) end = buf.size();
    vocab->word_to_index.emplace(buf.substr(start, end - start),
                                 indices[i]);
    start = end + 1;
  }
}

void c2v_tok_set_special(void* handle, int32_t vocab_id, int32_t oov,
                         int32_t pad) {
  Vocab* vocab = vocab_by_id(static_cast<Tokenizer*>(handle), vocab_id);
  if (!vocab) return;
  vocab->oov = oov;
  vocab->pad = pad;
}

// buf: concatenated lines; offsets: n_rows+1 offsets into buf.
// Output arrays must be preallocated: src/path/tgt/mask (n_rows,
// max_contexts) C-contiguous, label (n_rows,).
void c2v_tok_tokenize(void* handle, const char* buf, const int64_t* offsets,
                      int32_t n_rows, int32_t max_contexts,
                      int32_t num_threads, int32_t* src, int32_t* path,
                      int32_t* tgt, float* mask, int32_t* label) {
  const Tokenizer* tok = static_cast<Tokenizer*>(handle);
  if (num_threads <= 1 || n_rows < 64) {
    tokenize_range(tok, buf, offsets, 0, n_rows, max_contexts, src, path,
                   tgt, mask, label);
    return;
  }
  std::vector<std::thread> threads;
  int32_t chunk = (n_rows + num_threads - 1) / num_threads;
  for (int32_t t = 0; t < num_threads; ++t) {
    int32_t begin = t * chunk;
    int32_t end = std::min(n_rows, begin + chunk);
    if (begin >= end) break;
    threads.emplace_back(tokenize_range, tok, buf, offsets, begin, end,
                         max_contexts, src, path, tgt, mask, label);
  }
  for (auto& thread : threads) thread.join();
}

}  // extern "C"
