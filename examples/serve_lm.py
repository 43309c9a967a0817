"""Serve a small LM with batched requests: prefill + decode with KV caches.

The DSM-cache analogy in action (DESIGN.md §2): the KV cache is the
device-local replica the paper's DSM cache kept per node — written through at
every decode step, never invalidated because the owner is the only writer.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-4b --batch 8
"""

import argparse

from repro.launch.serve import serve
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    toks = serve(args.arch, smoke=True, batch=args.batch,
                 prompt_len=args.prompt_len, gen=args.gen)
    print(f"[serve_lm] generated {toks.shape[0]}×{toks.shape[1]} tokens; "
          f"first request: {toks[0][:10].tolist()}")


if __name__ == "__main__":
    main()
