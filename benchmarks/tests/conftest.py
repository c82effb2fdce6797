"""CPU only; the repo root on the path so that ``benchmarks`` imports."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
