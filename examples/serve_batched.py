"""Batched serving example: continuous batched decode over a reduced
MiniCPM with the production serving loop (extra CLI flags pass
through, e.g. ``--dp``).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

import sys

from repro.launch.serve import main

if __name__ == "__main__":
    main(["--reduced", *sys.argv[1:]])
