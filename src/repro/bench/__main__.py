"""Entry point for ``python -m repro.bench``."""

from repro import compile_cache
from repro.bench.cli import main

if __name__ == "__main__":
    compile_cache.enable()
    raise SystemExit(main())
