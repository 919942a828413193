// pjrt_tool — the standalone native featurizer (no Python in the loop).
//
// The dual-stack analog of the reference's Scala `DeepImageFeaturizer`
// (`src/main/scala/com/databricks/sparkdl/DeepImageFeaturizer.scala`†,
// SURVEY.md §3.5): where that stack ran a pre-frozen GraphDef through
// TensorFrames/JNI on JVM executors, this binary loads an exported
// StableHLO program directory (see `sparkdl_tpu.native.pjrt.export_program`),
// compiles it once on a PJRT plugin, uploads params once, then streams raw
// batches from a file through the device and appends features to the
// output file.
//
//   pjrt_tool <plugin.so> <program_dir> <input.bin> <output.bin>
//
// input.bin: concatenated batches; each batch is the program's data inputs
// back to back, dense row-major, exactly the dtypes/shapes in
// manifest.txt.  output.bin: the outputs of every batch, in order.
//
// Build: g++ -O2 -std=c++17 -I<tf-include> -o pjrt_tool pjrt_tool.cpp
//        _pjrt_runner.so -ldl   (or compile pjrt_runner.cpp in directly)

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

// C ABI from pjrt_runner.cpp
extern "C" {
struct PjrtRunner;
PjrtRunner* pjrt_runner_create(const char*, char*, int);
const char* pjrt_runner_last_error(PjrtRunner*);
int pjrt_runner_platform(PjrtRunner*, char*, int);
int64_t pjrt_runner_compile(PjrtRunner*, const char*, int64_t, const char*,
                            int64_t);
int64_t pjrt_runner_num_outputs(PjrtRunner*, int64_t);
int64_t pjrt_runner_put(PjrtRunner*, const void*, const char*,
                        const int64_t*, int32_t);
int64_t pjrt_runner_put_async(PjrtRunner*, const void*, const char*,
                              const int64_t*, int32_t);
int pjrt_runner_free_buffer(PjrtRunner*, int64_t);
int64_t pjrt_runner_execute(PjrtRunner*, int64_t, const int64_t*, int32_t,
                            int64_t*);
int64_t pjrt_runner_execute_async(PjrtRunner*, int64_t, const int64_t*,
                                  int32_t, int64_t*);
int64_t pjrt_runner_buffer_size(PjrtRunner*, int64_t);
int pjrt_runner_get(PjrtRunner*, int64_t, void*, int64_t);
void pjrt_runner_destroy(PjrtRunner*);
}

namespace {

struct Spec {
  std::string kind;   // "param" | "input" | "output"
  std::string dtype;  // short name ("f32", "u8", ...)
  std::vector<int64_t> dims;
  size_t bytes = 0;
};

size_t dtype_size(const std::string& d) {
  if (d == "f64" || d == "s64" || d == "u64") return 8;
  if (d == "f32" || d == "s32" || d == "u32") return 4;
  if (d == "f16" || d == "bf16" || d == "s16" || d == "u16") return 2;
  return 1;  // u8/s8/pred
}

bool read_file(const std::string& path, std::string* out) {
  std::ifstream f(path, std::ios::binary);
  if (!f) return false;
  std::ostringstream ss;
  ss << f.rdbuf();
  *out = ss.str();
  return true;
}

int die(PjrtRunner* r, const char* what) {
  std::fprintf(stderr, "pjrt_tool: %s: %s\n", what,
               r ? pjrt_runner_last_error(r) : "(no runner)");
  if (r) pjrt_runner_destroy(r);
  return 1;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc != 5) {
    std::fprintf(
        stderr,
        "usage: %s <plugin.so> <program_dir> <input.bin> <output.bin>\n",
        argv[0]);
    return 2;
  }
  const std::string plugin = argv[1], dir = argv[2], in_path = argv[3],
                    out_path = argv[4];

  // --- manifest ---
  std::ifstream mf(dir + "/manifest.txt");
  if (!mf) {
    std::fprintf(stderr, "pjrt_tool: cannot open %s/manifest.txt\n",
                 dir.c_str());
    return 1;
  }
  std::vector<Spec> params, inputs, outputs;
  std::string line;
  while (std::getline(mf, line)) {
    std::istringstream ls(line);
    Spec s;
    std::string dims;
    if (!(ls >> s.kind >> s.dtype >> dims)) continue;
    if (dims != "scalar") {
      std::istringstream ds(dims);
      std::string tok;
      while (std::getline(ds, tok, ',')) s.dims.push_back(std::stoll(tok));
    }
    s.bytes = dtype_size(s.dtype);
    for (int64_t d : s.dims) s.bytes *= static_cast<size_t>(d);
    (s.kind == "param" ? params : s.kind == "input" ? inputs : outputs)
        .push_back(s);
  }

  std::string program, copts, params_bin;
  if (!read_file(dir + "/program.mlir", &program) ||
      !read_file(dir + "/compile_options.pb", &copts) ||
      !read_file(dir + "/params.bin", &params_bin)) {
    std::fprintf(stderr, "pjrt_tool: missing program artifacts in %s\n",
                 dir.c_str());
    return 1;
  }

  // --- plugin + compile + resident params ---
  char err[4096];
  PjrtRunner* r = pjrt_runner_create(plugin.c_str(), err, sizeof(err));
  if (!r) {
    std::fprintf(stderr, "pjrt_tool: create failed: %s\n", err);
    return 1;
  }
  char platform[64];
  pjrt_runner_platform(r, platform, sizeof(platform));
  int64_t exec_id = pjrt_runner_compile(
      r, program.data(), static_cast<int64_t>(program.size()), copts.data(),
      static_cast<int64_t>(copts.size()));
  if (exec_id < 0) return die(r, "compile");

  std::vector<int64_t> arg_ids;
  size_t off = 0;
  for (const Spec& s : params) {
    if (off + s.bytes > params_bin.size()) {
      std::fprintf(stderr, "pjrt_tool: params.bin shorter than manifest\n");
      pjrt_runner_destroy(r);
      return 1;
    }
    int64_t id = pjrt_runner_put(r, params_bin.data() + off, s.dtype.c_str(),
                                 s.dims.data(),
                                 static_cast<int32_t>(s.dims.size()));
    if (id < 0) return die(r, "param upload");
    arg_ids.push_back(id);
    off += s.bytes;
  }

  // --- stream batches ---
  size_t batch_bytes = 0;
  for (const Spec& s : inputs) batch_bytes += s.bytes;
  std::ifstream in(in_path, std::ios::binary);
  std::ofstream out(out_path, std::ios::binary | std::ios::trunc);
  if (!in || !out) {
    std::fprintf(stderr, "pjrt_tool: cannot open input/output file\n");
    pjrt_runner_destroy(r);
    return 1;
  }
  // Double-buffered streaming: batch i+1's host read + host->device
  // transfer + execute are ENQUEUED (put_async/execute_async) before
  // batch i's outputs are fetched, so the link transfer and compute of
  // consecutive batches overlap instead of serializing — previously every
  // stage awaited its event before the next began (0.33 s/batch pure
  // serialized link time on a slow host<->device link).  One batch in
  // flight bounds device memory at 2x inputs + 2x outputs.
  std::vector<char> batch(batch_bytes);
  size_t n_batches = 0;
  const size_t n_params = arg_ids.size();
  struct InFlight {
    std::vector<int64_t> input_ids;
    std::vector<int64_t> output_ids;
  };
  InFlight prev;
  bool have_prev = false;

  auto drain = [&](InFlight& f) -> bool {  // fetch, write, free
    for (int64_t id : f.output_ids) {
      int64_t sz = pjrt_runner_buffer_size(r, id);
      if (sz < 0) return false;
      std::vector<char> host(static_cast<size_t>(sz));
      if (pjrt_runner_get(r, id, host.data(), sz) != 0) return false;
      out.write(host.data(), sz);
      pjrt_runner_free_buffer(r, id);
    }
    for (int64_t id : f.input_ids) pjrt_runner_free_buffer(r, id);
    return true;
  };

  while (true) {
    if (batch_bytes == 0) {
      if (n_batches) break;  // params-only program: run exactly once
    } else if (!in.read(batch.data(),
                        static_cast<std::streamsize>(batch_bytes))) {
      if (in.gcount() != 0) {
        std::fprintf(stderr,
                     "pjrt_tool: input.bin has a trailing partial batch "
                     "(%lld of %zu bytes) — batch shape mismatch?\n",
                     static_cast<long long>(in.gcount()), batch_bytes);
        pjrt_runner_destroy(r);
        return 1;
      }
      break;
    }
    InFlight cur;
    size_t boff = 0;
    for (const Spec& s : inputs) {
      // async put: the plugin stages the bytes during the call, so
      // `batch` is reusable for the next read while the transfer rides
      // under the previous batch's execute
      int64_t id = pjrt_runner_put_async(
          r, batch.data() + boff, s.dtype.c_str(), s.dims.data(),
          static_cast<int32_t>(s.dims.size()));
      if (id < 0) return die(r, "batch upload");
      cur.input_ids.push_back(id);
      boff += s.bytes;
    }
    arg_ids.resize(n_params);
    arg_ids.insert(arg_ids.end(), cur.input_ids.begin(),
                   cur.input_ids.end());
    cur.output_ids.resize(outputs.size() ? outputs.size() : 1);
    int64_t n_out = pjrt_runner_execute_async(
        r, exec_id, arg_ids.data(), static_cast<int32_t>(arg_ids.size()),
        cur.output_ids.data());
    if (n_out < 0) return die(r, "execute");
    cur.output_ids.resize(static_cast<size_t>(n_out));
    // with batch i+1 queued, draining batch i overlaps its fetch with
    // i+1's transfer+compute
    if (have_prev && !drain(prev)) return die(r, "fetch");
    prev = std::move(cur);
    have_prev = true;
    ++n_batches;
  }
  if (have_prev && !drain(prev)) return die(r, "fetch");
  std::fprintf(stderr, "pjrt_tool: platform=%s batches=%zu -> %s\n",
               platform, n_batches, out_path.c_str());
  pjrt_runner_destroy(r);
  return 0;
}
