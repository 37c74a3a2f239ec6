#!/usr/bin/env python
"""jaxlint — JAX/TPU tracing-hazard static analyzer with a CI ratchet.

Pure-AST: runs instantly, never imports jax (safe on images where the TPU
plugin makes ``import jax`` slow or fatal).  See ``pdnlp_tpu/analysis/``
for the rules (R1-R7) and README.md for the rule table + suppression
syntax.

Usage:
    python lint_tpu.py                         # scan the standard surface
    python lint_tpu.py --json pdnlp_tpu scripts serve_tpu.py
    python lint_tpu.py --fix-hints             # show suggested rewrites
    python lint_tpu.py --write-baseline        # re-record the ratchet
    python lint_tpu.py --list-rules
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pdnlp_tpu.analysis.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
