import os
import sys

# The benchmark is imported as ``benchmarks.chip`` from the repository root.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
