"""The chip benchmark's own tests. They import the harness from
benchmarks/chip (it is a program's directory, not a package on the path)
and need no chip: no test here describes a TPU topology."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if CHIP_DIR not in sys.path:
    sys.path.insert(0, CHIP_DIR)
