import os
import sys

# the benchmark is imported as ``chipbench`` from the root of the checkout
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
