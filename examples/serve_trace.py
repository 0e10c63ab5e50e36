"""End-to-end serving driver: continuous batching under a bursty arrival
trace, comparing dLLM-Serve against the three baseline systems.

    PYTHONPATH=src python examples/serve_trace.py [--workload burst] [--n 10]

This is the paper's Fig.3/4 experiment in miniature: same engine, same
workload, four system profiles (Fast-dLLM, dLLM-Cache, Sparse-dLLM, ours).
"""
import argparse

from repro.launch.serve import run_serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="burst",
                    choices=["livebench", "burst", "osc"])
    ap.add_argument("--rps", type=float, default=2.0)
    ap.add_argument("--n", type=int, default=10)
    args = ap.parse_args()

    print(f"workload={args.workload} rps={args.rps} n={args.n}\n")
    rows = []
    for system in ("fast-dllm", "dllm-cache", "sparse-dllm", "dllm-serve"):
        r = run_serve("llada-8b", system, args.workload, args.rps, args.n,
                      time_scale=0.02, hbm_bytes=16 << 30)
        rows.append(r)
        print(f"{system:12s} tput={r['throughput_tok_s']:8.1f} tok/s  "
              f"avg_lat={r['avg_latency']:7.2f}s  p99={r['p99_latency']:7.2f}s")
    best = max(r["throughput_tok_s"] for r in rows[:-1])
    print(f"\ndLLM-Serve speedup vs best baseline: "
          f"{rows[-1]['throughput_tok_s']/best:.2f}x  (paper: 1.61-1.81x)")


if __name__ == "__main__":
    main()
