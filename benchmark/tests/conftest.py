import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(BENCH, "loadgen")):
    if p not in sys.path:
        sys.path.insert(0, p)
