"""``python -m repro.serve`` — the coloring-service CLI smoke
(repro.serve.coloring.main)."""
from ..compile_cache import enable_compile_cache
from .coloring import main

if __name__ == "__main__":
    enable_compile_cache()
    main()
