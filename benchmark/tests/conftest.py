import os
import sys

# the benchmark's modules import each other by their own names; the
# reference tests compare with the program, from the repo root
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
