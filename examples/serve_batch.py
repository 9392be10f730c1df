"""End-to-end serving driver: batched requests, prefill + decode engine.

    PYTHONPATH=src python examples/serve_batch.py
"""

import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


if __name__ == "__main__":
    enable_compile_cache()
    serve_main(["--arch", "gemma3-1b", "--requests", "12", "--max-new", "16"])
