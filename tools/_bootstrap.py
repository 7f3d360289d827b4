"""Shared bootstrap for `python tools/<x>.py` invocations: puts the repo
root on sys.path (the interpreter only adds the SCRIPT's directory, tools/,
so the package would otherwise not import).
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
