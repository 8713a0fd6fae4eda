"""Batched serving: prefill + lock-step decode over a mixed batch of
requests (different prompt lengths, greedy & sampled), reporting
prefill latency and generated tokens per second.

Run:  PYTHONPATH=src python examples/serve_batch.py --arch qwen2-vl-2b
"""

import argparse

import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import BatchServer, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    print(f"serving {cfg.name} ({cfg.param_count() / 1e6:.1f}M reduced)")
    server = BatchServer(cfg, make_local_mesh(), max_len=128)

    rng = np.random.default_rng(0)
    requests = [
        Request(i,
                rng.integers(0, cfg.vocab_size,
                             int(rng.integers(4, 32))).astype(np.int32),
                max_new=args.gen,
                temperature=0.8 if i % 2 else 0.0)
        for i in range(args.batch)
    ]
    stats = server.serve(requests)
    gen = sum(len(t) for t in stats["outputs"].values())
    print(f"prefill: {stats['prefill_s'] * 1e3:.1f} ms  |  "
          f"{gen / (stats['prefill_s'] + stats['decode_s']):.1f} generated "
          f"tok/s")
    for rid, toks in stats["outputs"].items():
        mode = "sampled" if requests[rid].temperature > 0 else "greedy"
        print(f"  req {rid} ({mode}, prompt {len(requests[rid].prompt)}): "
              f"{toks[:10]}...")


if __name__ == "__main__":
    main()
