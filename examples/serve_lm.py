"""Serve a small model with batched requests: prefill + autoregressive
decode with ring-buffer/sequence KV caches.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch.serve import main
from repro.runtime.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    argv = sys.argv[1:] or [
        "--arch", "mixtral-8x7b", "--reduced", "--batch", "4",
        "--prompt-len", "32", "--gen", "16",
    ]
    raise SystemExit(main(argv))
