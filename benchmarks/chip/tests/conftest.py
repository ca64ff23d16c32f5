import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
