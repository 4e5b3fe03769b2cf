"""Serving example: batched requests through the continuous-batching engine
with int8 LUT tables (the paper's deployment mode), chunked prefill, and
nucleus sampling, on qwen3_1p7b cut to 4 layers, width 128, vocab 512 so it
runs on a laptop CPU.

  PYTHONPATH=src python examples/serve_batched.py
"""

from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    serve_main([
        "--arch", "qwen3_1p7b", "--layers", "4", "--d-model", "128",
        "--vocab", "512", "--requests", "8", "--slots", "4",
        "--prefill-chunk", "8", "--temperature", "0.8", "--top-p", "0.95",
        "--seed", "0",
    ])
