"""Batched LM inference through the platform, at the reduced config on CPU.

A thin caller of ``repro.launch.serve``: client requests enter the
``serve_lm`` composition, whose ``generate`` vertex drives the
continuous-batching engine, so the model is the payload and the platform
owns admission, memory contexts and engine scheduling. Any of the
assigned architectures is selectable with --arch; every other option of
``repro.launch.serve`` is accepted too and overrides the defaults below.
Exits non-zero unless every request finished.

    PYTHONPATH=src python examples/serve_lm.py --arch olmoe-1b-7b --requests 12
"""
import sys

from repro.launch.serve import main

SMOKE_DEFAULTS = [
    "--smoke", "--arch", "granite-8b", "--requests", "12", "--max-new", "8",
    "--cache-len", "32", "--min-prompt", "3", "--max-prompt", "11",
]

if __name__ == "__main__":
    sys.exit(main(SMOKE_DEFAULTS + sys.argv[1:]))
