"""Batched serving: prefill + greedy decode with per-layer KV/state caches.

    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-130m

Runs the same serve_prefill/serve_step functions the multi-pod dry-run
lowers for the decode_32k / long_500k cells (here on reduced configs).
"""

import argparse

from repro.launch.serve import main as serve_main
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    args = ap.parse_args()
    enable_compile_cache()
    serve_main(["--arch", args.arch, "--smoke", "--batch", "4",
                "--prompt-len", "32", "--gen", "16"])


if __name__ == "__main__":
    main()
