import os
import pathlib
import sys

# the benchmark's tests run on the CPU, with the program from src/
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = str(pathlib.Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
