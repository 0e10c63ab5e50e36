"""Paper Fig.7: relative throughput (dLLM-Serve / Sparse-dLLM) vs input and
output length. The paper observes speedups decaying from ~3.1x to ~2.5x as
lengths grow (longer atomic Refresh phases are harder to interleave)."""
from benchmarks._grid import CPU_HBM_BYTES
from repro.launch.serve import run_serve


def _pair(workload, in_len, out_len, seed=0):
    kw = dict(max_seq_len=256, block_size=8, steps_per_block=8, max_slots=10,
              max_num_batched_tokens=1024, max_num_logits=128,
              length_scale=1.0, time_scale=0.02, hbm_bytes=CPU_HBM_BYTES)
    import repro.data.workloads as W
    orig = W.make_trace

    def fixed_trace(name, n, rps, seed=0, scale=1.0):
        tr = orig(name, n, rps, seed, scale)
        return [W.TraceRequest(t.arrival, in_len, out_len) for t in tr]

    W.make_trace = fixed_trace
    try:
        ours = run_serve("llada-8b", "dllm-serve", workload, 2.0, 8,
                         seed=seed, **kw)
        base = run_serve("llada-8b", "sparse-dllm", workload, 2.0, 8,
                         seed=seed, **kw)
    finally:
        W.make_trace = orig
    return ours["throughput_tok_s"] / max(base["throughput_tok_s"], 1e-9)


def run(quick: bool = True):
    out = []
    in_lens = (16, 64, 128) if quick else (16, 32, 64, 96, 128)
    for il in in_lens:
        sp = _pair("livebench", il, 32)
        out.append((f"sensitivity/input_len{il}", 0.0,
                    f"{sp:.2f}x_vs_sparse-dllm"))
    out_lens = (16, 64) if quick else (16, 32, 64, 96)
    for ol in out_lens:
        sp = _pair("livebench", 48, ol)
        out.append((f"sensitivity/output_len{ol}", 0.0,
                    f"{sp:.2f}x_vs_sparse-dllm"))
    out.append(("sensitivity/claim", 0.0,
                "paper:3.1x->2.45x_decaying_with_input_len"))
    return out
