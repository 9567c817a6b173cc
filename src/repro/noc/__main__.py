"""``python -m repro.noc`` — dispatch to the CLI (repro.noc.cli)."""

import sys

from repro import compile_cache

from .cli import main

if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
